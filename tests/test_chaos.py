"""Chaos harness: deterministic fault injection + the serving soak under
fault.

Everything here is CPU-only, seed-deterministic, and fast — the suite is
tier-1 (`make test-chaos` selects just it). The correctness bar for the
serving soak is unchanged from `test_serve.py`: every stream
byte-identical to its solo decode, ≤ 2 compiled step programs — now with
transient step failures, page-pool exhaustion, and a mid-run engine
crash + restart() injected underneath it.
"""

import time

import numpy as np
import pytest

from tensorframes_tpu.models import TransformerLM
from tensorframes_tpu.obs import metrics as obs_metrics
from tensorframes_tpu.serve import GenerationEngine
from tensorframes_tpu.utils import chaos, get_config, set_config
from tensorframes_tpu.utils.chaos import ChaosFault
from tensorframes_tpu.utils.failures import (
    DeviceOOMError,
    PagePoolExhausted,
    is_oom,
    is_transient,
)

pytestmark = pytest.mark.chaos

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    return TransformerLM.init(0, VOCAB, d_model=16, n_heads=4, max_len=48)


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


def _prompts(rng, lens):
    return [
        rng.integers(1, VOCAB, size=n).astype(np.int32).tolist() for n in lens
    ]


def _solo(lm, prompt, n, **kw):
    return lm.generate(np.asarray([prompt], np.int32), n, **kw)[
        0, len(prompt):
    ]


# ---------------------------------------------------------------------------


class TestHarness:
    def test_disabled_is_a_noop(self):
        assert not chaos.enabled()
        chaos.site("serve.decode_step")  # any name, nothing happens
        chaos.site("no.such.site")

    def test_unknown_site_in_spec_never_fires_elsewhere(self):
        with chaos.scoped("other.site=fatal"):
            chaos.site("serve.decode_step")  # different site: no fire

    def test_every_nth_schedule(self):
        with chaos.scoped("s=transient:every=3"):
            fired = []
            for i in range(9):
                try:
                    chaos.site("s")
                    fired.append(False)
                except RuntimeError:
                    fired.append(True)
        assert fired == [False, False, True] * 3

    def test_times_caps_injections(self):
        with chaos.scoped("s=transient:times=2"):
            raised = 0
            for _ in range(10):
                try:
                    chaos.site("s")
                except RuntimeError:
                    raised += 1
        assert raised == 2

    def test_probability_schedule_is_seed_deterministic(self):
        def pattern():
            out = []
            with chaos.scoped("seed=9;s=transient:p=0.3"):
                for _ in range(50):
                    try:
                        chaos.site("s")
                        out.append(0)
                    except RuntimeError:
                        out.append(1)
            return out

        a, b = pattern(), pattern()
        assert a == b
        assert 0 < sum(a) < 50  # actually probabilistic, not all/nothing

    def test_kinds_match_the_failure_taxonomy(self):
        with chaos.scoped(
            "t=transient;o=oom;p=pool;f=fatal;l=latency:ms=30"
        ):
            with pytest.raises(RuntimeError) as ei:
                chaos.site("t")
            assert is_transient(ei.value) and not is_oom(ei.value)
            with pytest.raises(DeviceOOMError) as ei:
                chaos.site("o")
            assert is_oom(ei.value)
            with pytest.raises(PagePoolExhausted):
                chaos.site("p")
            with pytest.raises(ChaosFault) as ei:
                chaos.site("f")
            # the fatal kind must dodge BOTH classifiers — it exists to
            # exercise the fail-fast path
            assert not is_transient(ei.value) and not is_oom(ei.value)
            t0 = time.monotonic()
            chaos.site("l")  # latency injects, never raises
            assert time.monotonic() - t0 >= 0.03

    def test_injections_are_counted_by_site_and_kind(self):
        before = _counter_value(
            "chaos.injections_total", site="counted", kind="transient"
        )
        with chaos.scoped("counted=transient:every=2"):
            for _ in range(6):
                try:
                    chaos.site("counted")
                except RuntimeError:
                    pass
        assert (
            _counter_value(
                "chaos.injections_total", site="counted", kind="transient"
            )
            == before + 3
        )

    def test_malformed_specs_fail_loudly(self):
        # a typo'd schedule silently doing nothing would defeat the
        # harness; every malformed entry must raise at configure time
        for bad in (
            "s=notakind",
            "justaname",
            "s=transient:bogus=1",
            "s=transient:p",
        ):
            with pytest.raises(ValueError):
                set_config(chaos=bad)
            set_config(chaos="")

    def test_unrelated_set_config_keeps_schedule_state(self):
        with chaos.scoped("s=transient:every=2"):
            try:
                chaos.site("s")  # call 1 of 2
            except RuntimeError:
                pytest.fail("fired early")
            old = get_config().max_retries
            set_config(max_retries=old)  # unrelated touch mid-schedule
            with pytest.raises(RuntimeError):
                chaos.site("s")  # still call 2 -> fires

    def test_env_spec_drives_the_harness(self):
        import os
        import subprocess
        import sys

        code = (
            "from tensorframes_tpu.utils import chaos\n"
            "assert chaos.enabled(), chaos.active_spec()\n"
            "try:\n"
            "    chaos.site('x'); raise SystemExit('no injection')\n"
            "except RuntimeError as e:\n"
            "    assert 'UNAVAILABLE' in str(e)\n"
            "print('ENV_OK')\n"
        )
        env = dict(os.environ, TFT_CHAOS="x=transient", JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert "ENV_OK" in out.stdout, out.stderr


class TestSiteDrift:
    def test_every_package_call_site_is_declared_in_SITES(self):
        """Drift regression: a chaos site added to the package without a
        SITES entry would silently miss the configure-time unknown-site
        warning — a typo'd schedule for it would never fire and nobody
        would be told. Grep the package for literal
        ``chaos.site("...")`` call sites and assert both directions:
        every referenced name is declared, and every declared name has a
        call site (dead entries lie about coverage). Dynamically
        composed names (``"site." + suffix``, e.g. the fleet's
        per-replica kills) are out of grep scope by design — they ride
        a declared site's family."""
        import re
        from pathlib import Path

        import tensorframes_tpu

        root = Path(tensorframes_tpu.__file__).parent
        # every call form in the package: chaos.site("..."),
        # _chaos.site("..."), and the `site as _chaos_site` import alias
        pat = re.compile(
            r"""(?:_chaos\.site|chaos\.site|_chaos_site)"""
            r"""\(\s*["']([^"']+)["']\s*\)"""
        )
        referenced = {}
        sources = {}
        for path in sorted(root.rglob("*.py")):
            text = path.read_text()
            sources[path.name] = text
            for m in pat.finditer(text):
                referenced.setdefault(m.group(1), set()).add(path.name)
        assert referenced, "grep found no chaos.site call sites at all"
        unknown = {
            name: sorted(files)
            for name, files in referenced.items()
            if name not in chaos.SITES
        }
        assert not unknown, (
            f"chaos.site() call sites missing from chaos.SITES: {unknown} "
            f"— add them so a typo'd schedule warns at configure time"
        )
        # converse, softer (composed names like `"frame." + direction`
        # defeat the call-site grep): every declared site must at least
        # be MENTIONED in package source — a SITES entry nothing
        # references is a lie about coverage
        dead = [
            s
            for s in chaos.SITES
            if s not in referenced
            and not any(s in text for text in sources.values())
        ]
        assert not dead, (
            f"chaos.SITES entries never referenced in the package: {dead}"
        )

    def test_site_family_suffix_skips_unknown_site_warning(self, caplog):
        """``fleet.replica_fault.r1``-style names are a FAMILY site's
        runtime-composed children (``SITE_FAMILIES``): configuring one
        must not warn. A suffix on a NON-family site and a genuinely
        unknown name must both still warn — they are typos that would
        silently never fire."""
        import logging

        with caplog.at_level(logging.WARNING, logger="tensorframes_tpu.chaos"):
            with chaos.scoped("fleet.replica_fault.r9=fatal"):
                pass
        assert not any(
            "not one of the wired" in r.getMessage() for r in caplog.records
        )
        for typo in ("totally.bogus=fatal", "serve.decode_step.typo=fatal"):
            caplog.clear()
            with caplog.at_level(
                logging.WARNING, logger="tensorframes_tpu.chaos"
            ):
                with chaos.scoped(typo):
                    pass
            assert any(
                "not one of the wired" in r.getMessage()
                for r in caplog.records
            ), typo


class TestEngineDispatchSite:
    def test_batch_engine_retries_injected_transients(self, fast_retries):
        import tensorframes_tpu as tft
        from tensorframes_tpu.frame import TensorFrame

        before = _counter_value(
            "chaos.injections_total", site="engine.dispatch",
            kind="transient",
        )
        # times=1 — the first dispatch fails once (the device-resident
        # pass degrades to the synchronous chunked engine, whose retry
        # window runs the rows to completion)
        with chaos.scoped("engine.dispatch=transient:every=1:times=1"):
            df = TensorFrame.from_columns({"x": np.arange(8.0)})
            out = tft.map_rows(lambda x: {"y": x * 3.0}, df).collect()
        assert [r.y for r in out] == [3.0 * i for i in range(8)]
        assert (
            _counter_value(
                "chaos.injections_total", site="engine.dispatch",
                kind="transient",
            )
            > before
        )


class TestServingUnderChaos:
    def test_pool_exhaustion_injection_preempts_not_crashes(
        self, lm, fast_retries
    ):
        rng = np.random.default_rng(30)
        eng = GenerationEngine(lm, max_slots=3, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (5, 3, 6))
        before = _counter_value("failures.preemptions_total", op="serve")
        with chaos.scoped("seed=4;kv_pages.alloc=pool:every=6"):
            outs = eng.generate(prompts, max_new_tokens=8)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 8))
        assert _counter_value("failures.preemptions_total", op="serve") > before
        assert eng.pool.pages_in_use == 0
        assert eng.num_step_programs <= 2

    def test_chaos_soak_sixteen_requests_with_crash_and_restart(
        self, lm, fast_retries
    ):
        """The acceptance soak: the 16-request staggered run from
        test_serve.py, now under a seeded chaos schedule injecting
        transient step failures and page-pool exhaustion, plus one
        mid-run device-state crash + restart(). Every stream must stay
        byte-identical to its solo decode, every handle must finish
        inside its deadline, and recovery must add zero compiled
        programs."""
        rng = np.random.default_rng(8)
        eng = GenerationEngine(
            lm, max_slots=6, page_size=4, max_seq_len=40, num_pages=24
        )
        plens = [int(rng.integers(1, 13)) for _ in range(16)]
        nnews = [int(rng.integers(3, 15)) for _ in range(16)]
        prompts = _prompts(rng, plens)
        restarts_before = _counter_value("serve.engine_restarts_total")
        deadline = 120.0
        t0 = time.monotonic()
        handles = []
        with chaos.scoped(
            "seed=13;"
            "serve.decode_step=transient:p=0.15;"
            "serve.prefill=transient:p=0.05;"
            "kv_pages.alloc=pool:every=11"
        ):
            waves = [prompts[:5], prompts[5:9], prompts[9:13], prompts[13:]]
            k = 0
            for w, wave in enumerate(waves):
                for p in wave:
                    handles.append(eng.submit(p, nnews[k], deadline=deadline))
                    k += 1
                for _ in range(2):
                    eng.step()
                if w == 1:
                    # mid-run crash: device KV state is lost outright;
                    # restart() rebuilds it from host-side progress
                    eng.pool.fill(99.0, -99.0)
                    eng.restart()
            eng.run_until_idle()
        wall = time.monotonic() - t0
        assert wall < deadline  # no handle outlived its deadline budget
        for p, n, h in zip(prompts, nnews, handles):
            assert h.done and h.error is None
            np.testing.assert_array_equal(
                h.result(timeout=1), _solo(lm, p, n),
                err_msg=f"stream diverged (plen={len(p)}, n={n})",
            )
        assert eng.num_step_programs <= 2, eng.program_signatures
        assert eng.pool.pages_in_use == 0
        assert eng.healthy
        assert (
            _counter_value("serve.engine_restarts_total")
            == restarts_before + 1
        )
        # the schedule really did bite: both fault kinds fired
        assert (
            _counter_value(
                "chaos.injections_total", site="serve.decode_step",
                kind="transient",
            )
            > 0
        )
        assert (
            _counter_value(
                "chaos.injections_total", site="kv_pages.alloc", kind="pool"
            )
            > 0
        )

    def test_disabled_chaos_adds_no_programs(self, lm):
        """The overhead half of the acceptance bar that is assertable in
        a unit test: with no schedule installed the sites are inert and
        the engine still compiles exactly two step programs (the timing
        half — serving within noise of a build without the sites — is
        the serving cells' to show, PERF.md §4)."""
        assert not chaos.enabled()
        rng = np.random.default_rng(31)
        eng = GenerationEngine(lm, max_slots=2, page_size=4, max_seq_len=32)
        prompts = _prompts(rng, (3, 4))
        outs = eng.generate(prompts, max_new_tokens=5)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _solo(lm, p, 5))
        assert eng.num_step_programs <= 2
