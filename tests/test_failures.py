"""Failure handling: transient retry + OOM degradation.

The reference delegates all of this to Spark task retry (SURVEY §5); here
the engine owns it. Device failures are injected by patching the jit
wrappers — the classification layer only sees exception text, same as it
would from a real PJRT client.
"""

import numpy as np
import pytest

import tensorframes_tpu as tft
from tensorframes_tpu.engine import ops as engine_ops
from tensorframes_tpu.frame import TensorFrame
from tensorframes_tpu.utils import (
    DeviceOOMError,
    is_oom,
    is_transient,
    run_with_retries,
    set_config,
    get_config,
)


@pytest.fixture
def fast_retries():
    old = (get_config().max_retries, get_config().retry_backoff_s)
    set_config(max_retries=2, retry_backoff_s=0.001)
    yield
    set_config(max_retries=old[0], retry_backoff_s=old[1])


class TestClassification:
    def test_oom(self):
        assert is_oom(RuntimeError("RESOURCE_EXHAUSTED: while allocating"))
        assert is_oom(RuntimeError("Out of memory allocating 16G"))
        assert not is_oom(RuntimeError("UNAVAILABLE: socket closed"))

    def test_transient(self):
        assert is_transient(RuntimeError("UNAVAILABLE: connection reset"))
        assert is_transient(RuntimeError("DEADLINE_EXCEEDED: 30s"))
        assert not is_transient(ValueError("shapes do not match"))
        # OOM is NOT transient: identical retry cannot help
        assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED"))

    def test_markers_match_case_insensitively(self):
        # PJRT renders UNAVAILABLE, grpc-python unavailable, wrappers
        # anything between — the casing must not decide retryability
        assert is_transient(RuntimeError("unavailable: connection dropped"))
        assert is_transient(RuntimeError("Deadline_Exceeded: rpc wait"))
        assert is_transient(RuntimeError("Connection Reset by peer"))
        assert is_transient(RuntimeError("SOCKET CLOSED mid-write"))
        assert is_oom(RuntimeError("resource_exhausted: hbm"))
        assert is_oom(RuntimeError("OUT OF MEMORY while allocating"))
        assert is_oom(RuntimeError("oom during reduction"))

    def test_chained_cause_text_is_seen(self):
        # a wrapped PJRT status (`raise X from Y`) keeps its class
        def build(inner_msg, outer_msg="dispatch failed"):
            try:
                try:
                    raise RuntimeError(inner_msg)
                except RuntimeError as inner:
                    raise RuntimeError(outer_msg) from inner
            except RuntimeError as outer:
                return outer

        assert is_transient(build("UNAVAILABLE: preempted connection"))
        assert is_oom(build("RESOURCE_EXHAUSTED: hbm"))
        assert not is_transient(build("RESOURCE_EXHAUSTED: hbm"))
        assert not is_transient(build("just a bug"))
        # implicit __context__ (no `from`) must NOT leak retryability:
        # an unrelated error raised while HANDLING a transient one is
        # its own failure
        try:
            try:
                raise RuntimeError("UNAVAILABLE: flaky")
            except RuntimeError:
                raise ValueError("bug in the handler")
        except ValueError as e:
            assert not is_transient(e)

    def test_typed_oom_anywhere_in_chain(self):
        try:
            try:
                raise DeviceOOMError("pool dry")
            except DeviceOOMError as inner:
                raise RuntimeError("step failed") from inner
        except RuntimeError as e:
            assert is_oom(e) and not is_transient(e)

    def test_near_miss_strings_do_not_match(self):
        # "oom" must match as a word, not as a substring of zoom/room —
        # the old any-substring matching would break here once markers
        # went case-insensitive
        assert not is_oom(RuntimeError("zoom level 3 unsupported"))
        assert not is_oom(RuntimeError("the room is full"))
        assert not is_oom(RuntimeError("Bloom filter saturated"))
        assert is_oom(RuntimeError("OOM: killed"))
        assert is_oom(RuntimeError("device oom (16G requested)"))
        # "not available" is not "unavailable"
        assert not is_transient(RuntimeError("backend not available"))
        # a deadline that was merely mentioned is not the status marker
        assert not is_transient(RuntimeError("the deadline exceeded plan"))

    def test_deadline_exceeded_error_is_terminal(self):
        from tensorframes_tpu.utils import DeadlineExceededError

        e = DeadlineExceededError("request 7 exceeded its deadline")
        # a missed REQUEST deadline is caller-facing and final — unlike
        # a PJRT DEADLINE_EXCEEDED dispatch status, which retries
        assert not is_transient(e)
        assert not is_oom(e)
        assert isinstance(e, TimeoutError)


class TestRunWithRetries:
    def test_retries_then_succeeds(self, fast_retries):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("UNAVAILABLE: connection dropped")
            return 42

        assert run_with_retries(flaky) == 42
        assert len(calls) == 3  # initial + 2 retries

    def test_exhausts_and_raises(self, fast_retries):
        def always():
            raise RuntimeError("UNAVAILABLE: down")

        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            run_with_retries(always)

    def test_nontransient_raises_immediately(self, fast_retries):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("bug")

        with pytest.raises(ValueError):
            run_with_retries(bad)
        assert len(calls) == 1


class TestEngineIntegration:
    def test_map_blocks_transient_retried(self, fast_retries, monkeypatch):
        real = engine_ops._jitted
        state = {"failed": False}

        def flaky_jitted(g):
            fn = real(g)

            def wrapper(feed):
                if not state["failed"]:
                    state["failed"] = True
                    raise RuntimeError("UNAVAILABLE: injected")
                return fn(feed)

            return wrapper

        monkeypatch.setattr(engine_ops, "_jitted", flaky_jitted)
        df = TensorFrame.from_columns({"x": np.arange(6.0)})
        out = tft.map_blocks(lambda x: {"z": x + 1.0}, df).collect()
        assert [r.z for r in out] == [float(i + 1) for i in range(6)]
        assert state["failed"]

    def test_map_blocks_oom_says_repartition(self, fast_retries, monkeypatch):
        def oom_jitted(g):
            def wrapper(feed):
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")

            return wrapper

        monkeypatch.setattr(engine_ops, "_jitted", oom_jitted)
        df = TensorFrame.from_columns({"x": np.arange(6.0)})
        with pytest.raises(DeviceOOMError, match="repartition"):
            tft.map_blocks(lambda x: {"z": x + 1.0}, df).cache()

    def test_distributed_program_dispatch_retries(self, fast_retries):
        from tensorframes_tpu.parallel import distributed as D

        class G:
            pass

        calls = []

        def build():
            def prog(x):
                calls.append(1)
                if len(calls) < 2:
                    raise RuntimeError("UNAVAILABLE: injected")
                return x + 1

            return prog

        p = D._cached_program(G(), "k", build)
        assert p(1) == 2
        assert len(calls) == 2

    def test_map_rows_oom_halves_chunks(self, fast_retries, monkeypatch):
        real = engine_ops._jitted_vmap
        big_calls = []

        def limited_vmap(g):
            fn = real(g)

            def wrapper(feed):
                m = next(iter(feed.values())).shape[0]
                if m > 4:
                    big_calls.append(m)
                    raise RuntimeError("RESOURCE_EXHAUSTED: injected")
                return fn(feed)

            return wrapper

        monkeypatch.setattr(engine_ops, "_jitted_vmap", limited_vmap)
        df = TensorFrame.from_columns({"x": np.arange(20.0)})
        out = tft.map_rows(lambda x: {"y": x * 2.0}, df).collect()
        assert [r.y for r in out] == [float(2 * i) for i in range(20)]
        assert big_calls  # the halving path actually fired

    def test_reduce_blocks_streaming_path_correct(self, fast_retries):
        # force the host-streaming feeder (column over the cache budget):
        # reduce must take the per-partition sync path and stay correct
        old = get_config().device_cache_bytes
        set_config(device_cache_bytes=64)
        try:
            y = np.arange(40, dtype=np.float64).reshape(20, 2)
            df = TensorFrame.from_columns({"y": y}, num_partitions=4).analyze()
            s = tft.reduce_blocks(
                lambda y_input: {"y": y_input.sum(axis=0)}, df
            )
            np.testing.assert_allclose(np.asarray(s), y.sum(axis=0))
        finally:
            set_config(device_cache_bytes=old)

    def test_map_rows_single_row_oom_is_typed(self, fast_retries, monkeypatch):
        def always_oom(g):
            def wrapper(feed):
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")

            return wrapper

        monkeypatch.setattr(engine_ops, "_jitted_vmap", always_oom)
        df = TensorFrame.from_columns({"x": np.arange(4.0)})
        with pytest.raises(DeviceOOMError, match="one row per call"):
            tft.map_rows(lambda x: {"y": x * 2.0}, df).cache()


class _PoisonedResult:
    """Mimics a jax array whose async computation failed: shape metadata is
    readable (the dispatch-time checks pass), but any materialization —
    block_until_ready or conversion to numpy — raises the stored error."""

    def __init__(self, real):
        self._real = np.asarray(real)
        self.shape = self._real.shape
        self.nbytes = self._real.nbytes

    def block_until_ready(self):
        raise RuntimeError("UNAVAILABLE: injected mid-chain async failure")

    def __array__(self, *a, **k):
        raise RuntimeError("UNAVAILABLE: injected mid-chain async failure")


class TestMidChainRecovery:
    """A transient failure during ASYNC execution surfaces at
    materialization; the engine must re-run only the partitions whose
    outputs were lost — never the completed ones."""

    def _flaky_backend(self, fail_call_idx):
        real = engine_ops._jitted
        calls = []

        def jitted(g):
            fn = real(g)

            def wrapper(feed):
                idx = len(calls)
                calls.append(idx)
                res = fn(feed)
                if idx == fail_call_idx:
                    return {k: _PoisonedResult(v) for k, v in res.items()}
                return res

            return wrapper

        return jitted, calls

    def test_device_resident_chain_recovers_lost_partition(
        self, fast_retries, monkeypatch
    ):
        jitted, calls = self._flaky_backend(fail_call_idx=2)
        monkeypatch.setattr(engine_ops, "_jitted", jitted)
        df = TensorFrame.from_columns(
            {"x": np.arange(8.0)}, num_partitions=4
        )
        out = tft.map_blocks(lambda x: {"z": x * 10.0}, df).collect()
        assert [r.z for r in out] == [float(10 * i) for i in range(8)]
        # 4 partitions + exactly ONE recovery re-run: completed partitions
        # were not recomputed
        assert len(calls) == 5

    def test_streaming_mode_recovers_lost_partition(
        self, fast_retries, monkeypatch
    ):
        from tensorframes_tpu.utils import get_config, set_config

        jitted, calls = self._flaky_backend(fail_call_idx=1)
        monkeypatch.setattr(engine_ops, "_jitted", jitted)
        old = get_config().device_cache_bytes
        set_config(device_cache_bytes=64)  # force host-streaming drains
        try:
            df = TensorFrame.from_columns(
                {"x": np.arange(12.0)}, num_partitions=4
            )
            out = tft.map_blocks(lambda x: {"z": x + 5.0}, df).collect()
            assert [r.z for r in out] == [float(i + 5) for i in range(12)]
            assert len(calls) == 5
        finally:
            set_config(device_cache_bytes=old)

    def test_deterministic_failure_still_raises(
        self, fast_retries, monkeypatch
    ):
        # every run of partition 2 is poisoned: recovery must re-raise, not
        # loop
        real = engine_ops._jitted
        calls = []

        def jitted(g):
            fn = real(g)

            def wrapper(feed):
                idx = len(calls)
                calls.append(idx)
                res = fn(feed)
                if float(np.asarray(next(iter(res.values())))[0]) == 40.0:
                    return {k: _PoisonedResult(v) for k, v in res.items()}
                return res

            return wrapper

        monkeypatch.setattr(engine_ops, "_jitted", jitted)
        df = TensorFrame.from_columns(
            {"x": np.arange(8.0)}, num_partitions=4
        )
        with pytest.raises(RuntimeError, match="injected mid-chain"):
            tft.map_blocks(lambda x: {"z": x * 10.0}, df).collect()

    def test_demote_to_streaming_recovers_lost_partition(
        self, fast_retries, monkeypatch
    ):
        # trim maps have no static output-size estimate, so they start
        # device-resident and DEMOTE to host streaming when accumulated
        # bytes cross the budget mid-run — the demotion's host pulls must
        # recover lost results too
        from tensorframes_tpu.utils import get_config, set_config

        jitted, calls = self._flaky_backend(fail_call_idx=0)
        monkeypatch.setattr(engine_ops, "_jitted", jitted)
        old = get_config().device_cache_bytes
        set_config(device_cache_bytes=20)  # crosses after two partitions
        try:
            df = TensorFrame.from_columns(
                {"x": np.arange(8.0)}, num_partitions=4
            )
            out = tft.map_blocks(
                lambda x: {"z": x * 2.0}, df, trim=True
            ).collect()
            assert [r.z for r in out] == [float(2 * i) for i in range(8)]
            assert len(calls) == 5  # 4 partitions + 1 recovery
        finally:
            set_config(device_cache_bytes=old)
