"""Operations and HBM bytes of a coded training step, from shapes.

Counts are of the algorithm, not of what XLA emits: two FLOPs per
multiply-add of every matrix product (projections, MLP, the tied
logits), attention's two products (scores and the weighted sum), a
causal product counted at half, and training as three forward passes
(forward, and the backward's two products per forward product).  Norms,
softmax and the embedding gather are left out.  ``forward_flops`` is the
formula of a dense decoder (and encoder-decoder) with MHA/GQA attention
and a plain or gated MLP; a configuration of another shape (latent
attention, routed experts) brings its own count in its reference module.
"""
from __future__ import annotations


def _attn_proj(d_q: int, d_kv: int, m: dict) -> int:
    """Multiply-adds of q/k/v/o projections per position (self-attention)."""
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d_q * h * dh + 2 * d_kv * kv * dh + h * dh * d_q


def _mlp(m: dict) -> int:
    gated = m["activation"] in ("silu", "gelu")
    return (3 if gated else 2) * m["d_model"] * m["d_ff"]


def forward_flops(m: dict, seq_len: int) -> int:
    """FLOPs of one forward pass of one row (one sequence, and for an
    encoder-decoder one utterance of ``encoder_frames`` frames)."""
    d, s = m["d_model"], seq_len
    h, dh = m["n_heads"], m["head_dim"]
    per_layer = 2 * s * (_attn_proj(d, d, m) + _mlp(m))
    per_layer += 2 * s * s * h * dh          # causal scores + weighted sum
    total = m["n_layers"] * per_layer + 2 * s * d * m["vocab"]
    if "encoder_layers" in m:
        t = m["encoder_frames"]
        enc = 2 * t * (_attn_proj(d, d, m) + _mlp(m)) + 4 * t * t * h * dh
        cross = (2 * s * 2 * d * h * dh                  # q and o
                 + 2 * t * 2 * d * m["n_kv_heads"] * dh  # k and v of source
                 + 4 * s * t * h * dh)                   # scores + sum
        total += m["encoder_layers"] * enc + m["n_layers"] * cross
    return int(total)


def step_flops(m: dict, traffic: dict, k_shards: int, ranks: int,
               reference=None) -> dict:
    """Per-step FLOPs of the ranks a process holds.

    ``useful``: one unique shard per rank (what a user's step computes);
    ``backward``: each rank's K per-shard forward+backward passes, the
    coded redundancy included.  A forward pass is priced by the
    configuration's own ``forward_flops(model, seq_len)`` where its
    reference module (``perf/configs/<config>.py``) defines one, and by
    the dense formula here otherwise.
    """
    forward = getattr(reference, "forward_flops", forward_flops)
    shard = 3 * traffic["rows_per_shard"] * forward(m, traffic["seq_len"])
    return {"useful": ranks * shard, "backward": ranks * k_shards * shard}


def combine_bytes(n_params: int, k_shards: int, ranks: int) -> int:
    """HBM bytes of the fused combine per step, per rank: the (K, P)
    float32 shard-gradient stack read once and the (P,) contribution
    written once (coding rows and decode weights are resident)."""
    return ranks * (k_shards + 1) * 4 * int(n_params)
