"""The program's RWKV-6 against the plain reference of ``bench/configs/rwkv6-1.6b.py``
on the CPU, at a small size, on seeded random weights: the initial weights
drawn from one key, the forward pass (logits and loss), the first gradient
leaf by leaf, three AdamW steps, and a sequence whose length is not a
multiple of the WKV's chunk.

The program evaluates the WKV in chunks, the reference one position at a
time, so each checks the other.  Weights are the init recipe's with every
mixing coefficient, LoRA and decay bias moved off its initial value, so that
token shift, DDLerp, the decay and the bonus all carry weight."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import REPO
from bench import harness
from bench.reference import lr_at, make_ref_step
from repro.models import ModelConfig, forward_encode, forward_train, init_params
from repro.train import make_optimizer, make_train_state, make_train_step, optimizer_hypers

REF = harness._load_module(REPO / "bench" / "configs" / "rwkv6-1.6b.py")

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 2, "d_ff": 128, "vocab_size": 97,
         "rwkv_head_dim": 32, "rwkv_chunk": 8, "norm_eps": 1e-5}
# Float32 on the CPU: the chunked and the sequential WKV agree to about 1e-6
# here; 1e-4 leaves room for the sums' order over 2 layers and 3 steps.
TOL = 1e-4


def _cfg(**kw):
    return ModelConfig(arch_id="rwkv6-test", family="ssm", norm="layernorm",
                       **dict(MODEL, **kw)).validate()


def _params(seed=0):
    """The recipe's weights, each mixing coefficient, LoRA matrix and the
    decay bias moved off its initial value (decays spread over 0.05-0.95)."""
    p = init_params(jax.random.key(seed), _cfg())
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        key = jax.random.fold_in(jax.random.key(seed + 1), i)
        if name.endswith("['w0']"):
            x = jax.random.uniform(key, x.shape, minval=-3.0, maxval=1.0)
        elif any(s in name for s in ("mu_", "maa_", "w_lora", "scale", "bias")):
            x = x + 0.3 * jax.random.normal(key, x.shape)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _batch(seq, seed=0, batch=2):
    toks = jax.random.randint(jax.random.key(seed + 7), (batch, seq), 0, MODEL["vocab_size"])
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12))


def _leaves(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_init_draws_the_programs_weights():
    prog = _leaves(init_params(jax.random.key(3), _cfg()))
    ref = _leaves(REF.init_params(jax.random.key(3), MODEL))
    assert prog.keys() == ref.keys()
    assert "['ln0']['scale']" in ref and "['lm_head']['w']" in ref
    for name, x in ref.items():
        np.testing.assert_array_equal(prog[name], x, err_msg=name)


@pytest.mark.parametrize("seq,chunk,block", [(32, 8, 64), (37, 8, 16), (21, 32, 8)])
def test_forward_logits_and_loss(monkeypatch, seq, chunk, block):
    """Sequences of one and of several reference blocks and program chunks,
    whole or with a remainder."""
    monkeypatch.setattr(REF, "BLOCK", block)
    cfg, params, batch = _cfg(rwkv_chunk=chunk), _params(), _batch(seq)
    want = REF.logits(params, batch["tokens"], MODEL)
    got = forward_encode(params, {"tokens": batch["tokens"]}, cfg)
    assert _rel(got, want) < TOL
    loss, _ = forward_train(params, batch, cfg)
    assert abs(float(loss) - float(REF.loss(params, batch, MODEL))) < TOL


def test_first_gradient_leaf_by_leaf():
    cfg, params, batch = _cfg(), _params(1), _batch(40, seed=1)
    got = _leaves(jax.grad(lambda p: forward_train(p, batch, cfg)[0])(params))
    want = _leaves(jax.grad(REF.loss)(params, batch, MODEL))
    assert got.keys() == want.keys()
    errs = {n: _rel(got[n], w) for n, w in want.items()}
    assert max(errs.values()) < TOL, sorted(errs.items(), key=lambda kv: -kv[1])[:3]


def test_three_adamw_steps():
    cfg, params = _cfg(), _params(2)
    batches = [_batch(32, seed=10 + i) for i in range(3)]
    hp = {"lr": 1e-3, "warmup": 2, "total_steps": 100, "weight_decay": 0.1,
          "b1": 0.9, "b2": 0.95, "eps": 1e-8, "grad_clip": 1.0}
    opt = make_optimizer("adamw", optimizer_hypers("adamw", 100, hp))
    state = make_train_state(jax.random.key(0), cfg, opt)
    state = state._replace(params=params, opt_state=opt.init(params))
    step = jax.jit(make_train_step(cfg, opt))
    ref_step = make_ref_step(REF, MODEL, hp)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    rp, m, v = jax.tree_util.tree_map(jnp.copy, params), zeros(params), zeros(params)
    for t, b in enumerate(batches, start=1):
        state, metrics = step(state, b)
        rp, m, v, ref_loss = ref_step(rp, m, v, b, t, lr_at(t, hp))
        assert abs(float(metrics["loss"]) - float(ref_loss)) < TOL
    # Adam divides by the gradient's running size, so an element whose
    # gradient is near 0 moves by round-off: compare each leaf's change by
    # its norm, as the benchmark's change_gap does.
    got, want, init = _leaves(state.params), _leaves(rp), _leaves(params)
    norm = lambda n, t: float(np.linalg.norm(np.asarray(t[n] - init[n], np.float64)))
    gaps = {n: abs(norm(n, got) - norm(n, want)) / norm(n, want) for n in want}
    assert max(gaps.values()) < TOL, sorted(gaps.items(), key=lambda kv: -kv[1])[:3]

