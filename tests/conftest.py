"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so sharding tests exercise real
SPMD partitioning without TPU hardware: the env recipe below sets
``JAX_PLATFORMS=cpu`` and the forced device count before jax is imported,
which is all it takes.
"""

import importlib.util
import os
import sys

# The mesh width/axis and the env recipe come from ONE shared helper
# (sentinel_tpu/parallel/meshspec.py — also consumed by parallel/spmd.py,
# the __graft_entry__ dry-run, and the tier-4 SPMD analyzer subprocess).
# Loaded by FILE PATH: importing the sentinel_tpu package here would pull
# jax in before the env mutation below, defeating the whole point.
_ms_spec = importlib.util.spec_from_file_location(
    "_sentinel_meshspec",
    os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        os.pardir,
        "sentinel_tpu",
        "parallel",
        "meshspec.py",
    ),
)
_meshspec = importlib.util.module_from_spec(_ms_spec)
# registered so @dataclass can resolve the defining module at class
# creation (dataclasses looks the module up in sys.modules)
sys.modules[_ms_spec.name] = _meshspec
_ms_spec.loader.exec_module(_meshspec)
# keep_existing_count: a caller who pre-forced a topology keeps it
_meshspec.force_cpu_mesh_env(os.environ, keep_existing_count=True)

import jax  # noqa: E402

# NOTE: the tests keep no persistent compilation cache.  On jax 0.9.0 a
# second run reloads cached executables cleanly (chip_smoke.py's CPU
# rehearsal, run twice: 96 s of XLA compile cold, 16 s warm), so the entry
# points that run on the chip use one (utils/compile_cache.py); here a
# cache would only make a test's outcome depend on the run before.

import pytest  # noqa: E402

# Heavy equivalence/engine tests run EAGERLY (jax.disable_jit): their cost
# is XLA-CPU compilation of interpret-mode engine programs, not execution —
# the seg-vs-fused equivalence test alone took 1080 s jitted vs 96 s eager
# (measured, identical assertions; integer/float ops are bit-identical
# either way).  Modules needing real jit semantics (pjit/mesh sharding,
# subprocess hosts) stay jitted.
_EAGER_MODULES = {
    "test_engine_seg",
    "test_fused",
    "test_engine_backends",
    "test_client_fastpath",
    "test_rank",
    "test_occupy",
    "test_segment",
    "test_sketch",
    "test_tail_rules",
    "test_adapters",
    "test_mxu_table",
    "test_workload",
    "test_workload_adapters",
}


@pytest.fixture(autouse=True)
def _eager_heavy(request):
    # @pytest.mark.jitted opts a test back into compiled execution —
    # tests that run MANY small ticks are execution-bound, and eager
    # dispatch costs more there than one compile does
    if request.node.get_closest_marker("jitted") is not None:
        yield
        return
    mod = getattr(request.node, "module", None)
    name = mod.__name__.rsplit(".", 1)[-1] if mod else ""
    if name in _EAGER_MODULES:
        with jax.disable_jit():
            yield
    else:
        yield


@pytest.fixture(autouse=True)
def _clean_context():
    """Entries deliberately held open by one test must not leak into the
    next test's (or its asyncio.run copy's) context stack — the
    ContextTestUtil.cleanUpContext analog."""
    yield
    from sentinel_tpu.runtime import context as CTX

    CTX.clear()


@pytest.fixture(scope="module")
def closes_token_services():
    """A token service's column batcher keeps a daemon worker thread until
    the service is closed.  A module that builds services without closing
    them names this fixture (``pytestmark = pytest.mark.usefixtures(...)``):
    every ``DefaultTokenService`` built while it runs is closed when it ends,
    so its worker's later files do not inherit the threads."""
    from sentinel_tpu.cluster.token_service import DefaultTokenService

    built, init = [], DefaultTokenService.__init__

    def noted(self, *args, **kwargs):
        built.append(self)
        init(self, *args, **kwargs)

    DefaultTokenService.__init__ = noted
    yield
    DefaultTokenService.__init__ = init
    for service in built:
        if getattr(service, "col", None) is not None:
            service.close()


@pytest.fixture()
def vt():
    """Fresh virtual time source starting at a non-zero, non-aligned ms."""
    from sentinel_tpu.utils.time_source import VirtualTimeSource

    return VirtualTimeSource(start_ms=1_000)


@pytest.fixture()
def client_factory(vt):
    """Builds sync-mode clients on the small engine config + virtual time;
    stops them all at teardown."""
    from sentinel_tpu.core.config import small_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    made = []

    def factory(**kw):
        kw.setdefault("cfg", small_engine_config())
        kw.setdefault("time_source", vt)
        kw.setdefault("mode", "sync")
        c = SentinelClient(**kw)
        c.start()
        made.append(c)
        return c

    yield factory
    for c in made:
        c.stop()


@pytest.fixture()
def client(client_factory):
    """Shared sync-mode client on virtual time (the common fixture)."""
    return client_factory()
