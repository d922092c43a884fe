"""The gradient-coding Pallas kernels compile for a described TPU v5e.

Each case lowers one kernel for one chip of a ``v5e:2x2`` topology that
is described, not attached, and asserts the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  Widths are gc-lm-110m leaves (an
MLP matrix 768x3072, the tied embedding 32000x768) plus one ragged
width, which takes the kernels' masked tail.  Nothing runs: this guards
what interpret mode cannot see, such as layouts Mosaic refuses.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the test workers import
every test file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.gc_decode import decode_pallas
from repro.kernels.gc_encode import encode_pallas
from repro.kernels.gc_fused import encode_decode_pallas

WIDTHS = [768 * 3072, 32000 * 768, 768 * 3072 + 100]
N_PARITY = 2  # coded rows of the checkpoint encode (CodedSpec parity)


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _cases(k, d, dtype, sharding):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return {
        "fused": (encode_decode_pallas, (s(1), s(1, k), s(k, d))),
        "encode": (encode_pallas, (s(N_PARITY, k), s(k, d))),
        "decode": (decode_pallas, (s(k), s(k, d))),
    }


@pytest.mark.parametrize("kernel", ["fused", "encode", "decode"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("k", [2, 4, 8])
@pytest.mark.parametrize("d", WIDTHS)
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype, k, d):
    fn, args = _cases(k, d, dtype, one_chip)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
