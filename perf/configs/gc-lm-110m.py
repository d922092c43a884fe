"""Plain reference of gc-lm-110m: a pre-norm decoder-only LM.

Per layer: RMS norm, causal multi-head attention with rotary positions,
residual; RMS norm, SwiGLU MLP (silu(x Wg) * (x Wi)) Wo, residual.
Then a final RMS norm and logits against the tied token embedding.
Loss: mean next-token cross-entropy.  Parameters use the program's
layout (``embed.tok``, ``stack[0]`` stacked over the 12 layers,
``final_norm``), which the benchmark's weights fill from the seed.
"""
import jax

from perf import reference as R


def loss(model: dict, params, batch: dict, nm: R.Numerics = R.FP32):
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    tok = params["embed"]["tok"]
    base = float(model["rope_base"])
    x = nm.cast(tok[inputs])

    def layer(x, p):
        a = p["mixer"]
        h = R.rms_norm(x, p["norm_mix"]["scale"], nm)
        q = R.rope(nm.mm("bsd,dhx->bshx", h, a["wq"]), base, nm)
        k = R.rope(nm.mm("bsd,dhx->bshx", h, a["wk"]), base, nm)
        v = nm.mm("bsd,dhx->bshx", h, a["wv"])
        o = R.attention(q, k, v, causal=True, nm=nm)
        x = x + nm.mm("bshx,hxd->bsd", o, a["wo"])
        h = R.rms_norm(x, p["norm_ffn"]["scale"], nm)
        return x + R.swiglu(h, p["ffn"], nm), None

    x, _ = jax.lax.scan(layer, x, params["stack"][0])
    h = R.rms_norm(x, params["final_norm"]["scale"], nm)
    return R.xent(nm.mm("bsd,vd->bsv", h, tok), labels)
