"""The gradient-coding Pallas kernels compile for a described TPU v5e.

Each case lowers one kernel for one chip of a ``v5e:2x2`` topology that
is described, not attached, and asserts the compiled program holds the
Mosaic kernel (``tpu_custom_call``).  The encode/decode widths are
gc-lm-110m leaves (an MLP matrix 768x3072, the tied embedding
32000x768) plus one ragged width, which takes the kernels' masked tail;
the fused combine takes gc-lm-110m's (K, *shape) per-shard stacks, one
with a ragged last row block.  One more case compiles the whole
one-rank coded step and checks that the combine reads each per-shard
stack as the backward wrote it.  Nothing runs: this guards what
interpret mode cannot see, such as layouts Mosaic refuses.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the test workers import
every test file.
"""
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.core import Env, Plan, ShiftedExponential
from repro.dist.sharding import make_rules, use_mesh
from repro.kernels import gc_fused, ops
from repro.kernels.gc_decode import decode_pallas
from repro.kernels.gc_encode import encode_pallas
from repro.kernels.gc_fused import encode_decode_pallas
from repro.train.state import abstract_train_state
from repro.train.trainer import TrainConfig, make_coded_train_step

WIDTHS = [768 * 3072, 32000 * 768, 768 * 3072 + 100]
# gc-lm-110m leaves: the MLP stack, the tied embedding, and a ragged
# row count (the embedding's rows plus 100: a ragged last row block)
LEAVES = [(12, 768, 3072), (32000, 768), (32100, 768)]
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
        "encode": (encode_pallas, (s(N_PARITY, k), s(k, d))),
        "decode": (decode_pallas, (s(k), s(k, d))),
    }


@pytest.mark.parametrize("kernel", ["encode", "decode"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("k", [2, 4, 8])
@pytest.mark.parametrize("d", WIDTHS)
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype, k, d):
    fn, args = _cases(k, d, dtype, one_chip)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def chip_layouts(one_chip, monkeypatch):
    """The fused kernel reads stacks in the described chip's layouts."""
    (dev,) = one_chip.device_set
    monkeypatch.setattr(gc_fused, "_device", lambda: dev)
    return dev


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("k", [2, 4, 8])
@pytest.mark.parametrize("leaf", LEAVES, ids=lambda s: "x".join(map(str, s)))
def test_fused_compiles_for_v5e(one_chip, chip_layouts, leaf, dtype, k):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (s((), jnp.float32), s((k,), jnp.float32), s((k,) + leaf, dtype))
    compiled = jax.jit(encode_decode_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [1, 4, 8])
def test_fused_grid_steps_per_gclm_step(chip_layouts, k):
    """The gc-lm-110m combine's grid steps per step on a v5e: 269,222 in
    (K, 512) lane tiles at any K; under 5,000 in blocks sized by bytes,
    each stack read in the chip's layout."""
    params = abstract_train_state(get_config("gc-lm-110m"))[0].params
    leaves = [tuple(a.shape) for a in jax.tree.leaves(params)]
    assert sum(pl.cdiv(math.prod(s), 512) for s in leaves) == 269_222
    steps = 0
    for shape in leaves:
        _, view, k_axis, block = gc_fused.fused_plan(k, shape, jnp.float32)
        steps += math.prod(pl.cdiv(v, b) for d, (v, b) in enumerate(
            zip(view, block)) if d != k_axis)
    assert steps < 5000, steps


def _producers(hlo: str) -> dict:
    """instruction name -> (opcode, element count, operand names)."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\((.*)", line)
        if m:
            count = int(np.prod([int(x) for x in m[3].split(",") if x]))
            out[m[1]] = (m[4], count, re.findall(r"%([\w.-]+)", m[5]))
    return out


def test_coded_step_combine_reads_stacks_without_relayout(one_chip,
                                                          chip_layouts,
                                                          monkeypatch):
    """The one-rank spmd coded step of rank 0 of N=8 ``xf`` (K=8), at
    reduced gc-lm-110m widths, compiled for one described v5e: every
    leaf's combine is a Mosaic kernel, and no copy, transpose, reshape
    or fusion of a K-stack's size produces a kernel's operand — the
    kernel reads each per-shard stack as the backward wrote it."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    dev = chip_layouts
    mesh = jax.sharding.Mesh(np.asarray([dev]).reshape(1, 1),
                             ("data", "model"))
    cfg = get_config("gc-lm-110m").reduced(n_layers=2, d_model=256)
    env = Env.iid(ShiftedExponential(mu=1e-3, t0=50.0), 8)
    rep = NamedSharding(mesh, PartitionSpec())
    with use_mesh(mesh, make_rules(cfg)):
        state, _ = abstract_train_state(cfg)
        plan = Plan.build(state.params, env, scheme="xf")
        k = plan.k_shards
        step = jax.jit(make_coded_train_step(cfg, TrainConfig(), plan,
                                             mesh=mesh, mode="spmd"))
        state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=rep), state)
        tokens = jax.ShapeDtypeStruct(
            (1, k, 1, 64), jnp.int32,
            sharding=NamedSharding(mesh, PartitionSpec("data")))
        dec_w = jax.ShapeDtypeStruct(plan.full_decode_weights().shape,
                                     jnp.float32, sharding=rep)
        hlo = step.lower(state, tokens, dec_w).compile().as_text()
    assert k == 8
    leaves = jax.tree.leaves(state.params)
    stacks = {k * int(np.prod(a.shape)) for a in leaves}
    prod = _producers(hlo)
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == len(leaves)
    for line in calls:
        *_, name = re.findall(r"%([\w.-]+)", line.split("custom-call(", 1)[1]
                              .split(")")[0])      # the stack: last operand
        while prod[name][0] == "bitcast":
            name = prod[name][2][0]
        op, count, _ = prod[name]
        assert count in stacks, (name, line)
        assert op not in ("copy", "transpose", "reshape", "fusion"), (
            f"{name} = {op}(...) relays out a K-stack for {line.strip()}")
