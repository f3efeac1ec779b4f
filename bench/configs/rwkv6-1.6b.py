"""Plain reference of RWKV-6 "Finch" (arXiv:2404.05892, section 4), in jax.numpy.

Per block, pre-norm with residual adds: ``x += time_mix(LN1(x))``, then
``x += channel_mix(LN2(x))``; a LayerNorm ``ln0`` on the embeddings before
block 0, ``ln_out`` after the last, an untied head and the token-mean cross
entropy.

- Token shift: ``x_{t-1}``, zeros before the first position.
- DDLerp (eqs. 13-14): ``xm = x + (x_{t-1} - x) * mu_x``;
  ``m_c = mu_c + tanh(xm A) B_c`` for c in r, k, v, w, g (the five A's side by
  side in one matrix); the input of c is ``x + (x_{t-1} - x) * m_c``.
- r, k, v, g: the mixed inputs times W_r, W_k, W_v, W_g.
- Decay (eqs. 15-16): ``d = w0 + tanh(x_w A_d) B_d``, ``w = exp(-exp(d))``.
- WKV (eqs. 17-18), per head, the token-by-token recurrence as a ``lax.scan``
  over positions (in blocks, for memory): ``y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)``,
  ``S_t = diag(w_t) S_{t-1} + k_t v_t^T``, ``S_0 = 0``.  It is written with
  elementwise products and sums, so no matmul precision touches it.
- Output (eq. 19): a LayerNorm per head (``ln_x``, epsilon 64e-5) of y, times
  ``SiLU(g)``, times W_o.
- Channel mix (eqs. 20-22): ``k' = lerp(x, x_{t-1}, mu_k) W_k'``,
  ``r' = lerp(x, x_{t-1}, mu_r) W_r'``, out ``sigmoid(r') * (relu(k')^2 W_v')``.
- LayerNorms: epsilon ``norm_eps`` (1e-5 in the configuration), with scale
  and bias.

Departures from the paper: none in the equations.  The five DDLerp channels
are stacked in the order r, k, v, w, g, the program's (the released code
orders them w, k, v, r, g; with random weights the order names nothing).
Weights are random: ``init_params`` draws the program's recipe from the same
key (standard deviations: embedding and head 0.02, the five d x d and the
channel mix's matrices 1/sqrt(fan_in), LoRA matrices 0.01, ``u`` 0.1; ``w0``
-6, the mixing coefficients 0, norm scales 1 and biases 0), where the released
model was trained.

Layers are a ``lax.scan`` over parameters stacked by layer, each recomputed
in the backward pass, so the reference fits the chip after a run's window.
``dtype`` is the compute type of activations, matmul inputs and the WKV's
inputs: float32 for the reference, bfloat16 for the control.  The WKV's state,
the norms' statistics and the loss's log-sum-exp stay in float32.
"""
import math

import jax
import jax.numpy as jnp

N_MIX, LORA_MIX, LORA_DECAY = 5, 32, 64
BLOCK = 64


def _normal(key, shape, scale):
    return jax.random.normal(key, shape) * scale


def _dense(key, shape):
    return _normal(key, shape, 1.0 / math.sqrt(shape[0]))


def _norm(d):
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def _layer(key, m):
    d, f = m["d_model"], m["d_ff"]
    n = m["rwkv_head_dim"]
    kt, kc = jax.random.split(key, 4)[:2]
    t = jax.random.split(kt, 10)
    c = jax.random.split(kc, 3)
    return {
        "norm1": _norm(d),
        "tm": {
            "mu_x": jnp.zeros((d,)),
            "mu_rkvwg": jnp.zeros((N_MIX, d)),
            "maa_w1": _normal(t[0], (d, N_MIX * LORA_MIX), 1e-2),
            "maa_w2": _normal(t[1], (N_MIX, LORA_MIX, d), 1e-2),
            "w0": jnp.full((d,), -6.0),
            "w_lora_a": _normal(t[2], (d, LORA_DECAY), 1e-2),
            "w_lora_b": _normal(t[3], (LORA_DECAY, d), 1e-2),
            "u": _normal(t[4], (d // n, n), 0.1),
            "w_r": _dense(t[5], (d, d)), "w_k": _dense(t[6], (d, d)),
            "w_v": _dense(t[7], (d, d)), "w_g": _dense(t[8], (d, d)),
            "w_o": _dense(t[9], (d, d)),
            "ln_x_scale": jnp.ones((d,)), "ln_x_bias": jnp.zeros((d,)),
        },
        "norm2": _norm(d),
        "cm": {
            "mu_k": jnp.zeros((d,)), "mu_r": jnp.zeros((d,)),
            "w_k": _dense(c[0], (d, f)), "w_v": _dense(c[1], (f, d)),
            "w_r": _dense(c[2], (d, d)),
        },
    }


def init_params(key, m):
    ks = jax.random.split(key, 5)
    d, vocab = m["d_model"], m["vocab_size"]
    layers = [_layer(jax.random.fold_in(ks[1], r), m) for r in range(m["n_layers"])]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": {"tok": _normal(ks[0], (vocab, d), 0.02)},
        "ln0": _norm(d),
        "stack": [{"blocks": [stacked]}],
        "final_norm": _norm(d),
        "lm_head": {"w": _normal(ks[2], (d, vocab), 0.02)},
    }


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """r, k, v, w: (B, S, H, N) float32; u: (H, N).  The recurrence, one
    position at a time; returns y (B, S, H, N).  Positions are scanned in
    blocks of ``BLOCK``, each recomputed in the backward pass, so that the
    backward keeps one state per block and not one per position."""
    b, s, h, n = r.shape

    def step(state, xs):
        rt, kt, vt, wt = xs                                  # (B, H, N)
        kv = kt[..., :, None] * vt[..., None, :]             # (B, H, N, N)
        y = jnp.sum(rt[..., :, None] * (state + u[..., :, None] * kv), axis=-2)
        return wt[..., :, None] * state + kv, y

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    nb = -(-s // BLOCK)
    pad = ((0, 0), (0, nb * BLOCK - s), (0, 0), (0, 0))      # after the last position
    xs = tuple(jnp.pad(a, pad).reshape(b, nb, BLOCK, h, n).transpose(1, 2, 0, 3, 4)
               for a in (r, k, v, w))
    _, ys = jax.lax.scan(block, jnp.zeros((b, h, n, n), jnp.float32), xs)
    return ys.reshape(nb * BLOCK, b, h, n)[:s].swapaxes(0, 1)


def _time_mix(x, p, m, dtype):
    b, s, d = x.shape
    n = m["rwkv_head_dim"]
    h = d // n
    c = lambda a: a.astype(dtype)
    sx = _shift(x) - x
    xm = x + sx * c(p["mu_x"])
    lora = jnp.tanh(xm @ c(p["maa_w1"])).reshape(b, s, N_MIX, LORA_MIX)
    mix = jnp.einsum("bsnr,nrd->nbsd", lora, c(p["maa_w2"])) + c(p["mu_rkvwg"])[:, None, None]
    xr, xk, xv, xw, xg = x[None] + sx[None] * mix
    heads = lambda a: a.reshape(b, s, h, n).astype(jnp.float32)
    r, k, v = (heads(xi @ c(p[wn])) for xi, wn in ((xr, "w_r"), (xk, "w_k"), (xv, "w_v")))
    g = jax.nn.silu(xg @ c(p["w_g"]))
    dec = c(p["w0"]) + jnp.tanh(xw @ c(p["w_lora_a"])) @ c(p["w_lora_b"])
    w = jnp.exp(-jnp.exp(heads(dec)))
    y = _wkv(r, k, v, w, c(p["u"]).astype(jnp.float32))
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean(jnp.square(y - mu), -1, keepdims=True)
    y = ((y - mu) * jax.lax.rsqrt(var + 64e-5)).reshape(b, s, d)
    y = (y * p["ln_x_scale"] + p["ln_x_bias"]).astype(dtype)
    return (y * g) @ c(p["w_o"])


def _channel_mix(x, p, dtype):
    c = lambda a: a.astype(dtype)
    sx = _shift(x) - x
    k = jnp.square(jax.nn.relu((x + sx * c(p["mu_k"])) @ c(p["w_k"])))
    r = jax.nn.sigmoid((x + sx * c(p["mu_r"])) @ c(p["w_r"]))
    return r * (k @ c(p["w_v"]))


def _block(x, p, m, dtype):
    eps = m["norm_eps"]
    x = x + _time_mix(_layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps),
                      p["tm"], m, dtype)
    x = x + _channel_mix(_layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps),
                         p["cm"], dtype)
    return x


def logits(params, tokens, m, dtype=jnp.float32):
    eps = m["norm_eps"]
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(dtype)
    x = _layer_norm(x, params["ln0"]["scale"], params["ln0"]["bias"], eps)

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, m, dtype), None

    x, _ = jax.lax.scan(body, x, params["stack"][0]["blocks"][0])
    x = _layer_norm(x, params["final_norm"]["scale"], params["final_norm"]["bias"], eps)
    return (x @ params["lm_head"]["w"].astype(dtype)).astype(jnp.float32)


def loss(params, batch, m, dtype=jnp.float32):
    z = logits(params, batch["tokens"], m, dtype)
    lse = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)
