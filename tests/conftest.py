"""Test configuration.

Tests run on CPU with a virtual 8-device mesh so multi-chip sharding logic
(`tensorframes_tpu.parallel`) is exercised without TPU hardware, mirroring
how the reference tests distribution semantics on a `local[1]` Spark master
with explicit multi-partition RDDs
(`/root/reference/src/test/scala/org/tensorframes/TensorFlossTestSparkContext.scala:10-43`).

Env vars must be set before jax initializes its backends, hence here.
"""

import contextlib
import os
import tempfile

# force CPU whatever the machine holds: unit tests exercise sharding on 8
# virtual devices, not on a chip another process may own.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the tests' compile cache stays OUT of the checkout (the package default
# is <checkout>/.jax_cache): the chip tool copies the checkout as it
# stands on disk, and XLA:CPU executables built for this machine's CPU
# must not travel to another machine. A fixed path rather than a
# per-session one, so a second run here starts warm; setdefault, so an
# explicit operator/CI placement still wins. Subprocesses inherit it.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "tensorframes_tpu-tests", "jax-cache"),
)

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="session")
def _debug_bundles_in_tmp(tmp_path_factory):
    """Flight-recorder debug bundles (engine fatals, quarantines the
    fault suites deliberately trigger) land in the test session's tmp
    dir, not the developer's ~/.cache. setdefault so an explicit
    operator/CI TFT_DEBUG_DIR still wins."""
    os.environ.setdefault(
        "TFT_DEBUG_DIR", str(tmp_path_factory.mktemp("debug-bundles"))
    )


@pytest.fixture(autouse=True, scope="session")
def _program_costs_in_tmp(tmp_path_factory):
    """The program-cost registry's JSONL autopersist (obs/programs.py,
    fed by the time-series sampler tick) writes to the test session's
    tmp dir, not the developer's journal root."""
    os.environ.setdefault(
        "TFT_PROGRAM_COSTS_FILE",
        str(tmp_path_factory.mktemp("program-costs") / "programs.jsonl"),
    )


@pytest.fixture(autouse=True, scope="session")
def _request_ledger_in_tmp(tmp_path_factory):
    """The per-request cost ledger (obs/requests.py, fed by engine
    request completion) appends to the test session's tmp dir, not the
    developer's journal root."""
    os.environ.setdefault(
        "TFT_REQUESTS_FILE",
        str(tmp_path_factory.mktemp("request-costs") / "requests.jsonl"),
    )


@pytest.fixture(autouse=True, scope="session")
def _tune_store_in_tmp(tmp_path_factory):
    """The self-tuning layer's persisted store (tensorframes_tpu/tune)
    reads/writes the test session's tmp dir: tests must neither pollute
    the developer's store nor inherit its stale winners (a tuned
    block-row budget from an earlier run would silently change every
    map_rows plan under test). Unlike the debug/costs fixtures above
    this one FORCES the path — an inherited TFT_TUNE_FILE (e.g. the
    shared fleet store docs/tuning.md recommends exporting) would both
    leak winners INTO the tests and let the pin/clear/put drills wipe
    real fleet entries."""
    os.environ["TFT_TUNE_FILE"] = str(
        tmp_path_factory.mktemp("tune-store") / "tune.jsonl"
    )


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def gather_read(monkeypatch):
    """``with gather_read():`` — the serving engines built and run inside
    decode through the gather oracle ``ops.paged_attention`` in place of
    the ragged kernel (the engine's private ``_DECODE_READ`` hook), and
    count what it reads: every row of every slot's table."""
    import jax

    from tensorframes_tpu.ops import paged_attention
    from tensorframes_tpu.serve import engine

    def read(q, k_pages, v_pages, table, lengths, layer=None,
             block_pages=None):
        return paged_attention(q, k_pages, v_pages, table, lengths, layer)

    def positions(lengths, rows, page_size, page_bytes):
        return len(lengths) * rows * page_size

    @contextlib.contextmanager
    def use():
        with monkeypatch.context() as m:
            m.setattr(engine, "_DECODE_READ", (jax.jit(read), positions))
            yield

    return use
