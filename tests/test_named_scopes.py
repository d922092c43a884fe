"""The coded step names its layers, and the training loop its host work.

Each layer of the coded step runs under a ``jax.named_scope`` that must
reach the compiled HLO's ``op_name`` metadata, where a profiler trace's
device ops are named; ``Trainer.run`` marks each step and its host work
with profiler annotations on the same clock.
"""
import glob
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Env, Plan, ShiftedExponential
from repro.dist.sharding import make_rules, use_mesh
from repro.train.state import init_train_state
from repro.train.trainer import TrainConfig, Trainer, make_coded_train_step

SCOPES = ("per_shard_grad", "gc_pack", "gc_combine", "level_collective",
          "gc_unpack", "monitor_forward", "optimizer")


def _compiled_step_hlo(arch: str, scheme: str) -> tuple[str, int]:
    """HLO text of the spmd coded step on a 1 x 1 mesh (rank 0's share of
    N=4 workers), and its K."""
    cfg = get_config(arch).reduced(n_layers=1, d_model=32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    env = Env.iid(ShiftedExponential(mu=1e-3, t0=50.0), 4)
    with use_mesh(mesh, make_rules(cfg)):
        state, _ = init_train_state(cfg, jax.random.PRNGKey(0))
        plan = Plan.build(state.params, env, scheme=scheme)
        step = jax.jit(make_coded_train_step(cfg, TrainConfig(), plan,
                                             mesh=mesh, mode="spmd"))
        k = plan.k_shards
        args = [state, jnp.zeros((1, k, 1, 9), jnp.int32),
                plan.full_decode_weights().astype(np.float32)]
        if cfg.encoder is not None:
            args.append(jnp.zeros((1, k, 1, cfg.encoder.n_frames,
                                   cfg.d_model), jnp.float32))
        return step.lower(*args).compile().as_text(), k


@pytest.mark.parametrize("arch,scheme,k", [("gc-lm-110m", "xf", 4),
                                           ("gc-lm-110m", "uniform", 1),
                                           ("whisper-base", "xf", 4)])
def test_every_layer_scope_reaches_the_compiled_step(arch, scheme, k):
    text, got_k = _compiled_step_hlo(arch, scheme)
    assert got_k == k
    components = Counter(part for op in re.findall(r'op_name="([^"]*)"', text)
                         for part in op.split("/"))
    missing = [s for s in SCOPES if not components[s]]
    assert not missing, f"scopes absent from the compiled step: {missing}"
    # the levels' psum is emitted (over one device) and carries its scope
    psums = [line for line in text.splitlines() if " all-reduce(" in line]
    assert psums and all("/level_collective/" in line for line in psums)


def test_trainer_run_marks_each_step_and_its_host_work(tmp_path):
    cfg = get_config("gc-lm-110m").reduced(n_layers=1, d_model=32)
    tr = Trainer(cfg, TrainConfig(warmup=1, total_steps=10),
                 ShiftedExponential(mu=1e-3, t0=50.0), n_workers=3,
                 global_batch=3, seed=0)
    tr.run(1, log_every=0)                     # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.run(2, log_every=0)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = Counter(e.name for plane in pd.planes if plane.name.startswith(
        "/host:") for line in plane.lines for e in line.events)
    assert names["train"] == 2                 # StepTraceAnnotation per step
    for span in ("batch_build", "straggler_draw", "dispatch", "wait",
                 "metrics_sync"):
        assert names[span] == 2, (span, names[span])
    assert names["ckpt_save"] == 0             # no checkpointing configured
    assert [h["step"] for h in tr.history] == [1, 2, 3]
    assert all("wall_s" not in h for h in tr.history)
