"""Kernels of the served path compiled at their real widths for a described
v5e chip (no chip attached: nothing runs, so nothing here is a time or a
result).  Interpret mode cannot show what the chip's compiler refuses: a
slice off the tiling, a table past a kernel's fast memory.

One file, and the topology described inside a fixture: only one process may
hold the TPU's library, so only the worker that is given this file loads it;
where it cannot be described the tests skip."""

import jax
import jax.numpy as jnp
import pytest

from sentinel_tpu.ops import fused as FU

pytestmark = pytest.mark.jitted


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [256, 32768])
def test_scatter_sorted_compiles_at_the_gateway_stores_width(one_chip, rows):
    """The hot-parameter store of perfbench/configs/param-1m-hot-keys.json
    (2^22 cells a depth, the widest EngineConfig admits) at the light and
    the middle tick shape: a count plane and a concurrency plane, one digit
    each, as the tick's ``param{d}`` jobs have them."""
    from sentinel_tpu.core.config import PARAM_MAX_WIDTH

    def write(r, v):
        return FU.scatter_sorted(FU.Job("param0", PARAM_MAX_WIDTH, r, v, (1, 1)), interpret=False)

    r = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 2, rows), jnp.int32, sharding=one_chip)
    compiled = jax.jit(write).lower(r, v).compile()
    assert "scatter_sorted" in compiled.as_text()
