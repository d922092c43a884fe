"""Plain reference of whisper-base as this repository runs it.

Encoder (6 layers over 1500 frames): frame embeddings plus sinusoidal
positions (sin of the first d/2 channels, cos of the rest), then per
layer layer-norm, bidirectional attention with q/k/v biases, residual;
layer-norm, GELU MLP (tanh form), residual; a final layer-norm.

Decoder (6 layers over 448 tokens): token embedding, then per layer
layer-norm, causal self-attention with q/k/v biases and rotary
positions, residual; layer-norm, cross-attention over the encoder
output scaled by tanh(gate), residual; layer-norm, GELU MLP, residual.
A final layer-norm and logits against the tied token embedding.

Departures from the published model that the repository makes, and
this reference follows: the mel + convolution front end is a stub (the
frame embeddings are the input); the decoder uses rotary positions in
place of learned ones; cross-attention carries a tanh gate and no
biases.
"""
import jax
import jax.numpy as jnp
import numpy as np

from perf import reference as R


def _sinusoid(n: int, d: int) -> np.ndarray:
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10_000.0, 2 * dim / d)
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=1)


def _proj(nm, x, a, name):
    return nm.mm("bsd,dhx->bshx", x, a["w" + name]) + nm.cast(a["b" + name])


def encode(params, frames, nm: R.Numerics = R.FP32):
    enc = params["encoder"]
    x = nm.cast(frames) + nm.cast(jnp.asarray(
        _sinusoid(frames.shape[1], frames.shape[2]), jnp.float32))
    for lp in enc["layers"]:
        a = lp["mixer"]
        h = R.layer_norm(x, lp["norm_mix"], nm)
        o = R.attention(_proj(nm, h, a, "q"), _proj(nm, h, a, "k"),
                        _proj(nm, h, a, "v"), causal=False, nm=nm)
        x = x + nm.mm("bshx,hxd->bsd", o, a["wo"])
        x = x + R.gelu_mlp(R.layer_norm(x, lp["norm_ffn"], nm), lp["ffn"], nm)
    return R.layer_norm(x, enc["final_norm"], nm)


def loss(model: dict, params, batch: dict, nm: R.Numerics = R.FP32):
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    tok = params["embed"]["tok"]
    base = float(model["rope_base"])
    src = encode(params, batch["aux_inputs"], nm)
    x = nm.cast(tok[inputs])

    def layer(x, p):
        a = p["mixer"]
        h = R.layer_norm(x, p["norm_mix"], nm)
        q = R.rope(_proj(nm, h, a, "q"), base, nm)
        k = R.rope(_proj(nm, h, a, "k"), base, nm)
        o = R.attention(q, k, _proj(nm, h, a, "v"), causal=True, nm=nm)
        x = x + nm.mm("bshx,hxd->bsd", o, a["wo"])
        c = p["cross"]
        h = R.layer_norm(x, p["norm_cross"], nm)
        o = R.attention(nm.mm("bsd,dhx->bshx", h, c["wq"]),
                        nm.mm("bsd,dhx->bshx", src, c["wk"]),
                        nm.mm("bsd,dhx->bshx", src, c["wv"]),
                        causal=False, nm=nm)
        gate = nm.cast(jnp.tanh(c["gate"].astype(jnp.float32)))
        x = x + gate * nm.mm("bshx,hxd->bsd", o, c["wo"])
        h = R.layer_norm(x, p["norm_ffn"], nm)
        return x + R.gelu_mlp(h, p["ffn"], nm), None

    x, _ = jax.lax.scan(layer, x, params["stack"][0])
    h = R.layer_norm(x, params["final_norm"], nm)
    return R.xent(nm.mm("bsd,vd->bsv", h, tok), labels)
