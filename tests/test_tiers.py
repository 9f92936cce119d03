"""Disaggregated prefill/decode tiers: live KV-page slot migration.

The correctness bar is the same byte-identity contract every serve
feature carries, applied to a stream that MOVES between engines
mid-generation: a request prefilled on one replica and handed off to
another at first token — or rebalanced away from a pressured pool mid
decode — must stay BYTE-IDENTICAL to the same request decoded alone
through ``transformer_generate``, greedy and seeded alike, across
tensor-parallel degree changes, speculative-decoding asymmetry, and
prefix-cache/COW donors. Migration must add ZERO compiled step
programs (the snapshot restore writes pages with the same eager
indexing as COW materialization), and every failure at either chaos
site (``tier.handoff``, ``fleet.migrate``) must degrade to the
pre-tier behavior: keep decoding where the request already is, or
fall back to recompute-style preemption/replay — never a broken
stream.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.parallel import make_mesh
from tensorframes_tpu.serve import Fleet, GenerationEngine, QueueFullError
from tensorframes_tpu.serve.tiers import TierMigrationError
from tensorframes_tpu.utils import chaos, get_config, set_config

pytestmark = [pytest.mark.serve, pytest.mark.tiers]

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=64)


@pytest.fixture(scope="module")
def lm_tp():
    # 8 MHA heads so tp=2 slices whole KV heads (same shape as the
    # test_serve_tp module model)
    return TransformerLM.init(0, VOCAB, d_model=32, n_heads=8, max_len=64)


@pytest.fixture
def tier_knobs():
    old = (get_config().tier_handoff, get_config().tier_rebalance)
    yield
    set_config(tier_handoff=old[0], tier_rebalance=old[1])


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=3, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])


def _counter_value(name, **labels):
    try:
        return obs_metrics.registry().get(name).value(**labels)
    except KeyError:
        return 0.0


def _solo(lm, prompt, n, **kw):
    return lm.generate(np.asarray([prompt], np.int32), n, **kw)[
        0, len(prompt):
    ]


def _fleet(lm, n=2, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("watchdog_interval_s", 0.02)
    return Fleet(lm, replicas=n, **kw)


def _wait_for(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    pytest.fail(f"timed out waiting for {what}")


def _mixed_requests(seed, count, n_new=10):
    """(prompt, n, kwargs) triples alternating greedy / seeded."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(count):
        prompt = rng.integers(1, VOCAB, size=3 + i % 5).tolist()
        kw = {} if i % 2 == 0 else {"temperature": 0.7, "seed": 40 + i}
        reqs.append((prompt, n_new, kw))
    return reqs


def _step_until(eng, pred, what, max_steps=200):
    """Hand-step an engine that was never started until ``pred()``
    holds: the stream stops exactly there, with no stepping thread to
    run it past the point the test wants to act at."""
    for _ in range(max_steps):
        if pred():
            return
        eng.step()
    pytest.fail(f"{what}: not reached in {max_steps} engine steps")


def _drive(fleet, handles, max_rounds=500):
    """Run a fleet that was never started, on this thread, in a fixed
    order: one step per replica, then one router tick. A first-token
    handoff queued by a step is migrated by the tick that follows it,
    before the source can decode further — the threaded loops would
    race each other, and on this tiny model generation usually wins."""
    for _ in range(max_rounds):
        if all(h.done for h in handles):
            return
        for eng in fleet.engines:
            eng.step()
        fleet._tick()
    pytest.fail(f"streams unfinished after {max_rounds} rounds")


def _run_and_check(fleet, lm, reqs):
    """Submit every request, then assert byte-identity. An unstarted
    fleet is hand-driven (:func:`_drive`); a started one runs on its
    own threads."""
    handles = [
        fleet.submit(p, n, **kw) for p, n, kw in reqs
    ]
    if fleet._thread is None:
        _drive(fleet, handles)
    for h, (p, n, kw) in zip(handles, reqs):
        got = np.asarray(h.result(timeout=120))
        np.testing.assert_array_equal(
            got, _solo(lm, p, n, **kw),
            err_msg=f"prompt={p} kw={kw}",
        )


def _engine(lm, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_seq_len", 48)
    return GenerationEngine(lm, **kw)


@pytest.fixture(scope="module")
def src_engine(lm):
    """The export side of the engine-level tests: never started, stepped
    by hand. Every test detaches what it submitted, so the engine is
    idle again when the next one takes it."""
    return _engine(lm)


@pytest.fixture(scope="module")
def pd_fleet(lm):
    """One prefill + one decode replica, never started (hand-driven),
    shared by the tests that only submit through it."""
    fleet = _fleet(lm, 2, tiers=("prefill", "decode"))
    yield fleet
    fleet.stop()


# ---------------------------------------------------------------------------
# engine-level export / restore (no fleet in the loop)
# ---------------------------------------------------------------------------


class TestExportRestore:
    """Engines here are never started: the test steps them, so a slot
    is exported at an exact token count."""

    def _export_after(self, src, prompt, n, tokens, **kw):
        h = src.submit(prompt, n, **kw)
        _step_until(
            src, lambda: len(h._tokens) >= tokens,
            what=f"{tokens} token(s) before export",
        )
        snap = src.detach_slot(h.request_id)
        assert snap is not None
        return snap

    def test_unknown_request_returns_none(self, src_engine):
        assert src_engine.detach_slot(999_999) is None

    def test_engine_to_engine_byte_identity(self, lm, src_engine):
        dst = _engine(lm)
        # warm the destination's ordinary programs so the assertion
        # below isolates the attach itself (a cold engine would
        # compile its decode program on the first continued step
        # regardless of how the slot arrived)
        dst.submit([1, 2], 2)
        dst.run_until_idle()
        for kw in ({}, {"temperature": 0.6, "seed": 11}):
            prompt, n = [5, 3, 7, 1], 10
            snap = self._export_after(src_engine, prompt, n, 2, **kw)
            assert snap.n_pages >= 1 and snap.nbytes > 0
            assert len(snap.generated) < n  # exported mid-stream
            before = dst.num_step_programs
            h2 = dst.attach_slot(snap)
            dst.run_until_idle()
            rest = h2.result(timeout=60)
            got = np.asarray(list(snap.generated) + list(rest))
            np.testing.assert_array_equal(
                got, _solo(lm, prompt, n, **kw), err_msg=f"kw={kw}"
            )
            # restore writes pages eagerly — no new step programs
            assert dst.num_step_programs == before

    def test_still_prefilling_is_not_migratable(self, lm):
        eng = _engine(lm, prefill_chunk_tokens=4)
        h = eng.submit(list(range(1, 25)), 4)
        eng.step()  # one 4-token chunk of a 24-token prompt
        assert not h._tokens
        # before the first generated token the slot must not export
        assert eng.detach_slot(h.request_id) is None
        eng.run_until_idle()
        assert np.asarray(h.result(timeout=60)).shape == (4,)

    def test_geometry_mismatch_raises_and_leaves_dst_clean(
        self, lm, src_engine
    ):
        dst = _engine(lm, page_size=8)
        snap = self._export_after(src_engine, [2, 4, 6], 8, 1)
        free_before = dst.pool.pages_free
        with pytest.raises(TierMigrationError):
            dst.attach_slot(snap)
        assert dst.pool.pages_free == free_before

    def test_too_long_for_destination_raises(self, lm, src_engine):
        dst = _engine(lm, max_seq_len=16)
        snap = self._export_after(src_engine, list(range(1, 13)), 20, 1)
        with pytest.raises(TierMigrationError):
            dst.attach_slot(snap)

    def test_no_free_slot_raises_queue_full(self, lm, src_engine):
        dst = _engine(lm, max_slots=1)
        dst.submit([1, 2], 40)
        dst.step()  # seats the occupant; unstepped, it stays seated
        assert any(s is not None for s in dst.scheduler.slots)
        snap = self._export_after(src_engine, [3, 3, 3], 8, 1)
        free_before = dst.pool.pages_free
        with pytest.raises(QueueFullError):
            dst.attach_slot(snap)
        assert dst.pool.pages_free == free_before


# ---------------------------------------------------------------------------
# the byte-identity matrix through a tiered fleet
# ---------------------------------------------------------------------------


class TestHandoffByteIdentity:
    def test_greedy_and_seeded_streams_survive_handoff(self, lm, pd_fleet):
        before = _counter_value(
            "serve.kv_migrations_total", reason="handoff"
        )
        _run_and_check(pd_fleet, lm, _mixed_requests(3, 6, n_new=12))
        assert (
            _counter_value("serve.kv_migrations_total", reason="handoff")
            > before
        )
        # handoff restores compile nothing: both replicas stay at
        # the fleet's usual program budget
        assert all(n <= 2 for n in pd_fleet.program_counts().values())

    @pytest.mark.parametrize("direction", ["tp1_to_tp2", "tp2_to_tp1"])
    def test_hetero_tp_handoff(self, lm_tp, direction):
        meshes = [None, make_mesh({"tp": 2})]
        if direction == "tp2_to_tp1":
            meshes.reverse()
        fleet = Fleet(
            lm_tp,
            replicas=2,
            tiers=("prefill", "decode"),
            replica_kwargs=[{"mesh": m} for m in meshes],
            max_slots=4,
            page_size=4,
            max_seq_len=48,
            watchdog_interval_s=0.02,
        )
        try:
            before = _counter_value(
                "serve.kv_migrations_total", reason="handoff"
            )
            _run_and_check(fleet, lm_tp, _mixed_requests(7, 4, n_new=10))
            assert (
                _counter_value("serve.kv_migrations_total", reason="handoff")
                > before
            )
        finally:
            fleet.stop()

    @pytest.mark.parametrize("spec_on", ["prefill", "decode"])
    def test_speculative_asymmetry_handoff(self, lm, spec_on):
        """The draft KV page group exists on one side only: exported
        and dropped (prefill-side spec), or absent and re-derived from
        scratch (decode-side spec). Exact-match acceptance keeps the
        bytes pinned either way."""
        spec = {"draft_params": lm.params, "draft_len": 3}
        rk = [spec, {}] if spec_on == "prefill" else [{}, spec]
        fleet = Fleet(
            lm,
            replicas=2,
            tiers=("prefill", "decode"),
            replica_kwargs=rk,
            max_slots=4,
            page_size=4,
            max_seq_len=48,
            watchdog_interval_s=0.02,
        )
        try:
            before = _counter_value(
                "serve.kv_migrations_total", reason="handoff"
            )
            _run_and_check(fleet, lm, _mixed_requests(11, 4, n_new=12))
            assert (
                _counter_value("serve.kv_migrations_total", reason="handoff")
                > before
            )
        finally:
            fleet.stop()

    def test_prefix_cache_donor_handoff(self, lm):
        """A request seated on cached prefix pages (COW donor path)
        still migrates byte-identically once its first token lands —
        and a request still COW-materializing simply keeps decoding
        where it is (export refuses, nothing breaks)."""
        fleet = _fleet(
            lm, 2, tiers=("prefill", "decode"), prefix_cache=True
        )
        try:
            prompt = [4, 4, 8, 8, 2, 2, 6, 6]
            _run_and_check(fleet, lm, [(prompt, 6, {})])  # seeds the cache
            before = _counter_value(
                "serve.kv_migrations_total", reason="handoff"
            )
            reqs = [
                (prompt, 10, {}),
                (prompt, 10, {"temperature": 0.5, "seed": 21}),
            ]
            _run_and_check(fleet, lm, reqs)
            assert (
                _counter_value("serve.kv_migrations_total", reason="handoff")
                > before
            )
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# tier-aware routing
# ---------------------------------------------------------------------------


class TestTierRouting:
    def test_new_requests_prefer_the_prefill_tier(self, lm, tier_knobs):
        set_config(tier_handoff=False)  # freeze placement for inspection
        fleet = _fleet(lm, 2, tiers=("decode", "prefill"))
        try:
            fleet.start()
            h = fleet.submit([1, 2, 3], 4)
            assert fleet._inflight[h.request_id].replica.tier == "prefill"
            np.testing.assert_array_equal(
                np.asarray(h.result(timeout=60)), _solo(lm, [1, 2, 3], 4)
            )
        finally:
            fleet.stop()

    def test_untiered_fleet_never_migrates(self, lm):
        fleet = _fleet(lm, 2)
        try:
            before = obs_metrics.snapshot().get(
                "serve.kv_migrations_total", {}
            )
            _run_and_check(fleet, lm, _mixed_requests(5, 4))
            assert obs_metrics.snapshot().get(
                "serve.kv_migrations_total", {}
            ) == before
            assert all(rep.tier == "mixed" for rep in fleet._replicas)
        finally:
            fleet.stop()

    def test_handoff_config_off_stays_put(self, lm, pd_fleet, tier_knobs):
        set_config(tier_handoff=False)
        before = _counter_value(
            "serve.kv_migrations_total", reason="handoff"
        )
        _run_and_check(pd_fleet, lm, _mixed_requests(9, 3))
        assert (
            _counter_value("serve.kv_migrations_total", reason="handoff")
            == before
        )

    def test_no_decode_capacity_keeps_decoding_on_prefill(self, lm):
        # every replica is prefill: the handoff finds no destination
        # and the stream finishes where it prefilled — tiering can
        # never strand a request
        fleet = _fleet(lm, 2, tiers=("prefill", "prefill"))
        try:
            _run_and_check(fleet, lm, _mixed_requests(13, 3))
        finally:
            fleet.stop()

    def test_set_replica_tier_health_and_gauge(self, lm):
        fleet = _fleet(lm, 2, tiers=("prefill", "decode"))
        try:
            fleet.start()  # the supervisor publishes the per-tier gauge
            tiers = {
                n: h["tier"]
                for n, h in fleet.health()["replicas"].items()
            }
            assert sorted(tiers.values()) == ["decode", "prefill"]

            def _gauge(tier):
                return _counter_value("fleet.tier_replicas_active", tier=tier)

            _wait_for(
                lambda: _gauge("prefill") == 1.0 and _gauge("decode") == 1.0,
                what="per-tier gauge",
            )
            name = next(n for n, t in tiers.items() if t == "prefill")
            fleet.set_replica_tier(name, "mixed")
            assert fleet.health()["replicas"][name]["tier"] == "mixed"
            _wait_for(
                lambda: _gauge("prefill") == 0.0 and _gauge("mixed") == 1.0,
                what="gauge after re-tiering",
            )
            with pytest.raises(ValueError):
                fleet.set_replica_tier(name, "warp")
            with pytest.raises(KeyError):
                fleet.set_replica_tier("no-such-replica", "decode")
        finally:
            fleet.stop()

    def test_statusz_tiers_block(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        fleet = _fleet(lm, 2, tiers=("prefill", "decode"))
        try:
            _run_and_check(fleet, lm, _mixed_requests(17, 2))
            with ScoringServer(engine=fleet) as addr:
                status, body, _ = _http(addr, "GET", "/statusz")
            assert status == 200
            block = body["tiers"]
            assert sorted(block["replicas"].values()) == [
                "decode", "prefill",
            ]
            assert isinstance(block["migrations"], dict)
        finally:
            fleet.stop()

    def test_member_advertised_tier_reaches_the_roster(self, lm, tmp_path):
        """The multi-process wiring: a MemberAgent(tier=...) carries
        its role in the lease metadata, the router's sync applies it
        on join, and a later metadata change re-roles the replica."""
        from tensorframes_tpu.serve import GenerationEngine
        from tensorframes_tpu.serve.membership import (
            MemberAgent,
            MemberRegistry,
            connect_fleet,
        )

        eng = GenerationEngine(
            lm, max_slots=4, page_size=4, num_pages=64, max_seq_len=48,
            name="m0",
        )
        eng.start()
        agent = MemberAgent(
            eng,
            MemberRegistry(str(tmp_path), worker_id="proc-m0", ttl_s=5.0),
            "m0",
            tier="decode",
        )
        agent.start()
        fleet = None
        try:
            fleet = connect_fleet(
                str(tmp_path), worker_id="router", ttl_s=5.0,
                sync_interval_s=0.05, watchdog_interval_s=0.05,
            )
            fleet.start()
            _wait_for(
                lambda: "m0" in fleet.replica_names, what="member joining"
            )
            assert fleet.health()["replicas"]["m0"]["tier"] == "decode"
            with pytest.raises(ValueError):
                MemberAgent(eng, None, "bad", tier="warp")
        finally:
            if fleet is not None:
                fleet.stop()
                fleet.registry.stop(unlink_held=False)
            agent.shutdown(timeout_s=5.0)

    def test_statusz_tiers_none_when_all_mixed(self, lm):
        from tensorframes_tpu.interop.serving import ScoringServer

        fleet = _fleet(lm, 2)
        try:
            with ScoringServer(engine=fleet) as addr:
                status, body, _ = _http(addr, "GET", "/statusz")
            assert status == 200 and body["tiers"] is None
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# pool-pressure rebalancing: migrate instead of preempt
# ---------------------------------------------------------------------------


def _pressure_fleet(lm):
    # sized so the pinned replica overflows mid-decode but ONE
    # migration fully relieves it: 3 streams x 5 pages at full length
    # = 15 > 12 per-replica pages, while any 2 = 10 fit — fleet-wide
    # capacity (24) covers the whole workload, so zero preemptions is
    # actually achievable when rebalance works
    return Fleet(
        lm,
        replicas=2,
        max_slots=4,
        page_size=4,
        num_pages=12,
        max_seq_len=48,
        watchdog_interval_s=0.02,
    )


def _pressure_reqs():
    rng = np.random.default_rng(29)
    return [
        (rng.integers(1, VOCAB, size=8).tolist(), 12,
         {"temperature": 0.6, "seed": 60 + i})
        for i in range(3)
    ]


class TestRebalance:
    def test_pressure_migrates_instead_of_preempting(self, lm, tier_knobs):
        fleet = _pressure_fleet(lm)
        try:
            fleet.start()
            mig0 = _counter_value(
                "serve.kv_migrations_total", reason="rebalance"
            )
            pre0 = _counter_value("failures.preemptions_total", op="serve")
            reqs = _pressure_reqs()
            handles = [
                fleet.submit(p, n, session="hot", **kw) for p, n, kw in reqs
            ]
            for h, (p, n, kw) in zip(handles, reqs):
                np.testing.assert_array_equal(
                    np.asarray(h.result(timeout=120)),
                    _solo(lm, p, n, **kw),
                )
            assert (
                _counter_value(
                    "serve.kv_migrations_total", reason="rebalance"
                )
                > mig0
            )
            # migration absorbed the pressure: no preemption was paid
            assert (
                _counter_value("failures.preemptions_total", op="serve")
                == pre0
            )
        finally:
            fleet.stop()

    def test_rebalance_config_off_falls_back_to_preemption(
        self, lm, tier_knobs
    ):
        set_config(tier_rebalance=False)
        fleet = _pressure_fleet(lm)
        try:
            fleet.start()
            mig0 = _counter_value(
                "serve.kv_migrations_total", reason="rebalance"
            )
            pre0 = _counter_value("failures.preemptions_total", op="serve")
            reqs = _pressure_reqs()
            handles = [
                fleet.submit(p, n, session="hot", **kw) for p, n, kw in reqs
            ]
            for h, (p, n, kw) in zip(handles, reqs):
                np.testing.assert_array_equal(
                    np.asarray(h.result(timeout=120)),
                    _solo(lm, p, n, **kw),
                )
            assert (
                _counter_value(
                    "serve.kv_migrations_total", reason="rebalance"
                )
                == mig0
            )
            assert (
                _counter_value("failures.preemptions_total", op="serve")
                > pre0
            )
        finally:
            fleet.stop()


# ---------------------------------------------------------------------------
# chaos at the migration sites
# ---------------------------------------------------------------------------


class TestMigrationChaos:
    def test_fatal_export_aborts_and_stream_continues(self, lm, pd_fleet):
        ab0 = _counter_value("serve.kv_migrations_total", reason="aborted")
        ok0 = _counter_value("serve.kv_migrations_total", reason="handoff")
        with chaos.scoped("tier.handoff=fatal"):
            _run_and_check(pd_fleet, lm, _mixed_requests(19, 4))
        assert (
            _counter_value("serve.kv_migrations_total", reason="aborted")
            > ab0
        )
        assert (
            _counter_value("serve.kv_migrations_total", reason="handoff")
            == ok0
        )

    def test_transient_migrate_fault_retries_through(
        self, lm, pd_fleet, fast_retries
    ):
        ok0 = _counter_value("serve.kv_migrations_total", reason="handoff")
        with chaos.scoped("fleet.migrate=transient:every=2"):
            _run_and_check(pd_fleet, lm, _mixed_requests(23, 4, n_new=12))
        assert (
            _counter_value("serve.kv_migrations_total", reason="handoff")
            > ok0
        )


# ---------------------------------------------------------------------------
# HTTP plumbing for the statusz checks and the soak
# ---------------------------------------------------------------------------


def _http(addr, method, path, body=None):
    host, _, port = addr.rpartition(":")
    payload = b"" if body is None else json.dumps(body).encode()
    with socket.create_connection((host, int(port)), timeout=15) as c:
        c.sendall(
            (
                f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(payload)}\r\n"
                f"Connection: close\r\n\r\n"
            ).encode() + payload
        )
        buf = b""
        while True:
            chunk = c.recv(65536)
            if not chunk:
                break
            buf += chunk
    head, _, raw = buf.partition(b"\r\n\r\n")
    status = int(head.split(b"\r\n")[0].split(b" ", 2)[1])
    try:
        parsed = json.loads(raw.decode())
    except ValueError:
        parsed = {}
    return status, parsed, {}


def _stream_req(addr, body, timeout=15.0):
    """Streaming POST /generate; (status, tokens, terminal). A torn
    connection (the router died under us) returns what was read with
    terminal None instead of raising."""
    host, _, port = addr.rpartition(":")
    payload = json.dumps(dict(body, stream=True)).encode()
    c = socket.create_connection((host, int(port)), timeout=timeout)
    toks, terminal, status = [], None, 0
    try:
        c.sendall(
            (
                f"POST /generate HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(payload)}\r\n"
                f"Connection: close\r\n\r\n"
            ).encode() + payload
        )
        f = c.makefile("rb")
        status = int(f.readline().split(b" ", 2)[1])
        while f.readline() not in (b"\r\n", b""):
            pass
        if status != 200:
            try:
                terminal = json.loads(f.read().decode())
            except ValueError:
                terminal = {}
            return status, toks, terminal
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line.decode())
            if "t" in d:
                toks.append(int(d["t"]))
            else:
                terminal = d
                break
    except OSError:
        pass
    finally:
        c.close()
    return status, toks, terminal


def _resilient_stream(addrs, body, rid, timeout=240.0):
    """Drive one stream to completion across router deaths: reconnect
    with request_id + from=<delivered> against whichever router
    answers."""
    got = []
    deadline = time.monotonic() + timeout
    i = 0
    while time.monotonic() < deadline:
        addr = addrs[i % len(addrs)]
        i += 1
        req = dict(body, request_id=rid, **{"from": len(got)})
        try:
            status, toks, term = _stream_req(addr, req, timeout=10.0)
        except OSError:
            time.sleep(0.25)
            continue
        if status in (503, 409) or status == 0:
            time.sleep(0.25)  # standby / fenced / no answer: rotate
            continue
        assert status == 200, (status, term)
        got.extend(toks)
        if term is not None:
            if term.get("done"):
                return got, term
            pytest.fail(f"stream {rid} errored: {term}")
    pytest.fail(f"stream {rid} never finished")


def _read_report(path, timeout=90.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        time.sleep(0.1)
    pytest.fail(f"report {path} never appeared")


# each router subprocess hosts its OWN local tiered fleet (KV pages can
# only migrate between engines in one process) behind the shared
# router-HA lease + WAL dir: kill the active one and the standby's
# fleet replays the journal — prefill, handoff, resume — byte-identical
_TIER_ROUTER_SCRIPT = r"""
import json, os, sys, time
from tensorframes_tpu.interop.serving import ScoringServer
from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.serve import Fleet
from tensorframes_tpu.serve.router_ha import attach_router_ha
from tensorframes_tpu.utils.config import set_config

ha_dir, name, report = sys.argv[1], sys.argv[2], sys.argv[3]
set_config(router_wal=True)
lm = TransformerLM.init(0, 32, d_model=16, n_heads=4, max_len=64)
fleet = Fleet(
    lm, replicas=2, tiers=("prefill", "decode"), max_slots=8,
    page_size=4, num_pages=96, max_seq_len=64,
    watchdog_interval_s=0.05,
)
ha = attach_router_ha(fleet, ha_dir, name=name, ttl_s=2.0)
fleet.start()
srv = ScoringServer(engine=fleet, max_connections=32)
host, port = srv.start()
with open(report + ".tmp", "w") as f:
    json.dump({"addr": f"{host}:{port}"}, f)
os.rename(report + ".tmp", report)
while True:
    time.sleep(0.05)
"""


def _spawn(script, args, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-c", script, *args], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


@pytest.mark.slow
class TestKillSoak:
    def test_kill9_mid_migration_streams_resume_byte_identical(
        self, lm, tmp_path
    ):
        """The acceptance drill: two routers, each fronting a local
        prefill/decode fleet over the shared WAL dir; 12 client
        streams with chaos LATENCY injected at ``fleet.migrate`` on
        the active router so handoffs are reliably in flight when it
        takes kill -9. The standby seizes the lease, replays the
        journal recompute-style through its own tiered fleet (prefill
        -> handoff -> decode again), and every client finishes
        byte-identical to solo with zero lost or duplicated tokens."""
        ha_dir = str(tmp_path / "ha")
        os.makedirs(ha_dir)
        r1_report = str(tmp_path / "r1.json")
        r2_report = str(tmp_path / "r2.json")
        routers = {
            # stretch the export->restore window so the kill lands
            # mid-migration for some streams
            "r1": _spawn(
                _TIER_ROUTER_SCRIPT, [ha_dir, "r1", r1_report],
                extra_env={"TFT_CHAOS": "seed=3;fleet.migrate=latency:ms=40"},
            ),
        }
        try:
            r1_addr = _read_report(r1_report)["addr"]

            def _active(addr):
                try:
                    status, body, _ = _http(addr, "GET", "/statusz")
                except OSError:
                    return False
                return status == 200 and (
                    (body.get("router") or {}).get("active") is True
                )

            _wait_for(
                lambda: _active(r1_addr), timeout=120,
                what="r1 active with its tiered fleet",
            )
            routers["r2"] = _spawn(
                _TIER_ROUTER_SCRIPT, [ha_dir, "r2", r2_report],
            )
            r2_addr = _read_report(r2_report)["addr"]
            addrs = [r1_addr, r2_addr]

            rng = np.random.default_rng(31)
            reqs = []
            for i in range(12):
                prompt = rng.integers(1, VOCAB, size=3 + i % 4).tolist()
                kw = (
                    {} if i % 3 == 0
                    else {"temperature": 0.8, "seed": 70 + i}
                )
                reqs.append((prompt, 12, kw))
            want = [_solo(lm, p, n, **kw) for p, n, kw in reqs]

            results = [None] * len(reqs)
            errors = []

            def run_client(i):
                p, n, kw = reqs[i]
                body = {"prompt": p, "max_new_tokens": n, **kw}
                try:
                    results[i] = _resilient_stream(
                        addrs, body, rid=f"mig-{i}"
                    )
                except BaseException as e:  # pytest.fail raises
                    errors.append((i, repr(e)))

            threads = [
                threading.Thread(target=run_client, args=(i,), daemon=True)
                for i in range(len(reqs))
            ]
            for i, t in enumerate(threads):
                t.start()
                time.sleep(0.1)
                if i == 5:
                    # kill -9 the ACTIVE router with handoffs in flight
                    routers["r1"].kill()
            for t in threads:
                t.join(timeout=300)
            assert not errors, errors
            assert all(r is not None for r in results)
            for i, ((toks, term), w) in enumerate(zip(results, want)):
                np.testing.assert_array_equal(
                    np.asarray(toks), np.asarray(w), err_msg=f"mig-{i}"
                )
                assert term["tokens_total"] == len(w)

            # the standby owns the lease now, and its own tiered fleet
            # performed real handoffs while absorbing the replay
            status, body, _ = _http(r2_addr, "GET", "/statusz")
            assert status == 200
            assert body["router"]["active"] is True
            assert body["router"]["epoch"] >= 1
            tiers = body["tiers"]
            assert sorted(tiers["replicas"].values()) == [
                "decode", "prefill",
            ]
            assert any(
                "handoff" in str(k) and v > 0
                for k, v in tiers["migrations"].items()
            ), tiers["migrations"]
        finally:
            for proc in routers.values():
                if proc.poll() is None:
                    try:
                        proc.kill()
                    except OSError:
                        pass
            for proc in routers.values():
                proc.wait(timeout=30)
