"""Plain float32 building blocks for the configurations' references.

Nothing here imports the program.  Each configuration's own reference
(``perf/configs/<config>.py``) writes its forward pass and loss from
these pieces in straightforward ``jax.numpy``: full softmax attention,
no chunking, no kernels, no sharding.  ``Numerics`` fixes the
precision: the reference runs float32 with every matrix product at the
precision its configuration states (``stated``); a control runs the
same code with its activations and matmul operands in a lower type
(``perf/calibrate.py``).

The optimizer is written out too (global-norm clip, AdamW, warm-up then
cosine learning rate), so the reference can follow a training run step
by step from the same initial weights.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Numerics:
    """Activation type, matmul operand type and matmul precision."""

    act: Any = jnp.float32
    operand: Optional[Any] = None
    precision: Any = jax.lax.Precision.HIGHEST

    def cast(self, x):
        return x.astype(self.act)

    def mm(self, eq: str, a, b):
        def rnd(x):
            if self.operand is not None:
                x = x.astype(self.operand)
            return x.astype(self.act)

        out = jnp.einsum(eq, rnd(a), rnd(b), precision=self.precision,
                         preferred_element_type=jnp.float32)
        return out.astype(self.act)


FP32 = Numerics()

#: a configuration's ``matmul_precision``: how its float32 matrix
#: products run.  ``default`` is the platform's default, which on a TPU
#: is one bfloat16 pass with float32 accumulation.
PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "highest": jax.lax.Precision.HIGHEST}


def stated(model: dict) -> Numerics:
    """The numerics a configuration states: float32 activations, matrix
    products at its ``matmul_precision``."""
    return Numerics(precision=PRECISION[model["matmul_precision"]])


# ------------------------------------------------------------------ layers
def rms_norm(x, scale, nm: Numerics, eps: float = 1e-6):
    """RMS norm with the scale stored as (scale - 1)."""
    xf = x.astype(jnp.float32)
    out = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return nm.cast(out * (1.0 + scale.astype(jnp.float32)))


def layer_norm(x, p, nm: Numerics, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps)
    return nm.cast(out * p["scale"].astype(jnp.float32)
                   + p["bias"].astype(jnp.float32))


def rope(x, base: float, nm: Numerics):
    """Rotary embedding over (B, S, H, D), halves rotated as pairs
    (x[:D/2], x[D/2:]) at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = base ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return nm.cast(jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], axis=-1))


def attention(q, k, v, *, causal: bool, nm: Numerics):
    """Softmax attention.  q: (B, S, H, D); k, v: (B, T, Hkv, D)."""
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = nm.mm("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    if causal:
        s, t = scores.shape[-2:]
        allowed = np.arange(t)[None, :] <= np.arange(s)[:, None]
        scores = jnp.where(allowed, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return nm.mm("bhst,bthd->bshd", probs, v)


def swiglu(x, p, nm: Numerics):
    h = nm.mm("bsd,df->bsf", x, p["wi"])
    g = jax.nn.silu(nm.mm("bsd,df->bsf", x, p["wg"]).astype(jnp.float32))
    return nm.mm("bsf,fd->bsd", nm.cast(g * h.astype(jnp.float32)), p["wo"])


def gelu_mlp(x, p, nm: Numerics):
    h = nm.mm("bsd,df->bsf", x, p["wi"]).astype(jnp.float32)
    return nm.mm("bsf,fd->bsd", nm.cast(jax.nn.gelu(h, approximate=True)),
                 p["wo"])


def xent(logits, labels):
    """Mean next-token cross-entropy over every position."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# --------------------------------------------------------------- optimizer
def learning_rate(step: int, opt: dict) -> float:
    """Linear warm-up from 0, then cosine decay to a tenth."""
    lr, warmup, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if step < warmup:
        return lr * step / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * prog)))


def clip_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, grads, m, v, count: int, lr, opt: dict):
    """One AdamW step; ``count`` is the step number after this update."""
    b1, b2, wd = opt["b1"], opt["b2"], opt["weight_decay"]
    bc1, bc2 = 1.0 - b1 ** count, 1.0 - b2 ** count

    def one(p, g, m_, v_):
        m_ = b1 * m_ + (1.0 - b1) * g
        v_ = b2 * v_ + (1.0 - b2) * g * g
        upd = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8) + wd * p
        return p - lr * upd, m_, v_

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)
