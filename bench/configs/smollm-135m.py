"""Plain reference of SmolLM-135M (llama architecture), in jax.numpy.

Pre-norm decoder: RMSNorm, grouped-query attention with rotary positions
(rotate-half layout), SwiGLU MLP, residual adds, a final RMSNorm and a head
tied to the embedding; loss is the token-mean cross entropy.  Layers are a
``lax.scan`` over parameters stacked by layer, each recomputed in the
backward pass, so that a full-width batch fits one chip.

``init_params`` draws the weights the program's recipe draws from the same
key: embedding N(0, 0.02), every matrix N(0, 1/fan_in), norm scales 1.
``dtype`` is the compute type of activations and matmul inputs, float32
for the reference and bfloat16 for the control; the loss's log-sum-exp stays
in float32.
"""
import math

import jax
import jax.numpy as jnp


def _dense(key, shape):
    return jax.random.normal(key, shape) * (1.0 / math.sqrt(shape[0]))


def _layer(key, m):
    d, f = m["d_model"], m["d_ff"]
    hd = d // m["n_heads"]
    ka, km = jax.random.split(key, 4)[:2]
    qa, kk, kv, ko = jax.random.split(ka, 4)
    g, u, dn = jax.random.split(km, 3)
    return {
        "norm1": {"scale": jnp.ones((d,))},
        "attn": {"wq": _dense(qa, (d, m["n_heads"] * hd)),
                 "wk": _dense(kk, (d, m["n_kv_heads"] * hd)),
                 "wv": _dense(kv, (d, m["n_kv_heads"] * hd)),
                 "wo": _dense(ko, (m["n_heads"] * hd, d))},
        "norm2": {"scale": jnp.ones((d,))},
        "mlp": {"w_gate": _dense(g, (d, f)), "w_up": _dense(u, (d, f)),
                "w_down": _dense(dn, (f, d))},
    }


def init_params(key, m):
    ks = jax.random.split(key, 5)
    layers = [_layer(jax.random.fold_in(ks[1], r), m) for r in range(m["n_layers"])]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": {"tok": jax.random.normal(ks[0], (m["vocab_size"], m["d_model"])) * 0.02},
        "stack": [{"blocks": [stacked]}],
        "final_norm": {"scale": jnp.ones((m["d_model"],))},
    }


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale.astype(x.dtype)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def _block(x, p, m, dtype):
    b, s, d = x.shape
    h, kvh = m["n_heads"], m["n_kv_heads"]
    hd = d // h
    c = lambda w: w.astype(dtype)
    xn = _rms(x, p["norm1"]["scale"], m["norm_eps"])
    q = _rope((xn @ c(p["attn"]["wq"])).reshape(b, s, h, hd), m["rope_theta"])
    k = _rope((xn @ c(p["attn"]["wk"])).reshape(b, s, kvh, hd), m["rope_theta"])
    v = (xn @ c(p["attn"]["wv"])).reshape(b, s, kvh, hd)
    k, v = jnp.repeat(k, h // kvh, axis=2), jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(hd, dtype))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, jnp.asarray(-jnp.inf, scores.dtype))
    att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h * hd)
    x = x + o @ c(p["attn"]["wo"])
    xn = _rms(x, p["norm2"]["scale"], m["norm_eps"])
    mlp = p["mlp"]
    x = x + (jax.nn.silu(xn @ c(mlp["w_gate"])) * (xn @ c(mlp["w_up"]))) @ c(mlp["w_down"])
    return x


def loss(params, batch, m, dtype=jnp.float32):
    emb = params["embed"]["tok"]
    x = jnp.take(emb, batch["tokens"], axis=0).astype(dtype)

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, m, dtype), None

    x, _ = jax.lax.scan(body, x, params["stack"][0]["blocks"][0])
    x = _rms(x, params["final_norm"]["scale"], m["norm_eps"])
    logits = (x @ emb.T.astype(dtype)).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)
