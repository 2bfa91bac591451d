"""granite-4.0-h: the chunked SSD scan against the recurrence it computes,
the published layer order of the benchmark's cut, and the four granite
multipliers and NoPE attention against the equations they stand for
(``models/granite_hybrid.py``'s docstring)."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import granite_hybrid as gh
from repro.models import mamba2
from repro.models import module as nn
from repro.models.model_api import build_model
from repro.sharding.plan import make_plan

ROOT = Path(__file__).resolve().parents[1]
PLAN = make_plan(get_config("granite-4-h-micro").reduced(), None)


def _recurrence(x, dt, A, Bm, Cm, s0):
    """s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t;  y_t = s_t C_t, one step at a time."""
    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(dtt * A)[:, :, None, None] * s
             + jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt))
        return s, jnp.einsum("bhpn,bn->bhp", s, ct)

    seq = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    s, y = jax.lax.scan(step, s0, (seq(x), seq(dt), seq(Bm), seq(Cm)))
    return jnp.moveaxis(y, 0, 1), s


def _ssd_inputs(chunk, n_chunks):
    B, T, H, P, N = 2, chunk * n_chunks, 3, 4, 5
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    # slow decays: exp(dt A) ~ 0.97-0.995 a step, so state outlives a chunk
    dt = jax.random.uniform(ks[1], (B, T, H), minval=0.01, maxval=0.05)
    A = -jnp.array([0.5, 0.3, 0.1])
    Bm, Cm = jax.random.normal(ks[2], (B, T, N)), jax.random.normal(ks[3], (B, T, N))
    s0 = jax.random.normal(ks[4], (B, H, P, N))
    return x, dt, A, Bm, Cm, s0


def test_ssd_chunked_matches_the_sequential_recurrence():
    chunk = 8
    x, dt, A, Bm, Cm, s0 = _ssd_inputs(chunk, 4)
    y_ref, s_ref = _recurrence(x, dt, A, Bm, Cm, s0)
    y, s = mamba2.ssd_chunked(x, dt, A, Bm, Cm, s0, chunk=chunk)
    scale = float(jnp.max(jnp.abs(y_ref)))
    # float32 on the CPU: the two orders of summation agree to rounding
    np.testing.assert_allclose(y, y_ref, atol=1e-5 * scale, rtol=0)
    np.testing.assert_allclose(s, s_ref, atol=1e-5 * float(jnp.max(jnp.abs(s_ref))), rtol=0)

    # planted fault: the state reset at every chunk boundary
    zero = jnp.zeros_like(s0)
    cut = lambda a, i: a[:, i * chunk:(i + 1) * chunk]  # noqa: E731
    y_bad = jnp.concatenate([
        mamba2.ssd_chunked(cut(x, i), cut(dt, i), A, cut(Bm, i), cut(Cm, i),
                           s0 if i == 0 else zero, chunk=chunk)[0] for i in range(4)], axis=1)
    assert float(jnp.max(jnp.abs(y_bad - y_ref))) > 1e-2 * scale


def test_mamba_init_draws_the_published_ranges():
    cfg = get_config("granite-4-h-micro").reduced()
    p = mamba2.init_mamba_block(cfg, jax.random.key(3))
    A = np.exp(np.asarray(p["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["dt"]["bias"]))
    assert np.all((A >= 1) & (A <= 16)) and np.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001))


def test_l10_cut_runs_the_published_layers_0_to_9():
    doc = json.loads((ROOT / "bench/configs/granite-4-h-micro-l10.json").read_text())
    cfg = dataclasses.replace(get_config(doc["arch"]), n_layers=doc["num_hidden_layers"])
    published = tuple(get_config("granite-4-h-micro").layer_types)
    assert len(published) == 40 and published == tuple(doc["layer_types"])
    assert gh.layer_kinds(cfg) == published[:10]
    assert [i for i, k in enumerate(published) if k == "attention"] == [5, 15, 25, 35]
    assert gh.runs(cfg) == [("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 9)]
    # the benchmark runs the registry's values and its reference reads the file's
    ssm = cfg.ssm
    # the family's attention has no positional encoding, as the file's
    assert doc["position_embedding_type"] == "nope"
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling, cfg.norm_eps) == (
        doc["embedding_multiplier"], doc["residual_multiplier"], doc["attention_multiplier"],
        doc["logits_scaling"], doc["rms_norm_eps"])
    assert (ssm.state_dim, ssm.head_dim, ssm.d_conv, ssm.expand, ssm.chunk,
            ssm.expand * cfg.d_model // ssm.head_dim) == (
        doc["mamba_d_state"], doc["mamba_d_head"], doc["mamba_d_conv"], doc["mamba_expand"],
        doc["mamba_chunk_size"], doc["mamba_n_heads"])


def _small(**kw):
    return dataclasses.replace(get_config("granite-4-h-micro").reduced(), **kw)


def _logits(cfg, tokens, key=0):
    params = build_model(cfg).init(jax.random.key(key))
    return np.asarray(gh.forward(cfg, params, tokens, PLAN), np.float32)


TOKENS = jax.random.randint(jax.random.key(1), (2, 16), 0, 256, jnp.int32)


def test_logits_scaling_divides_the_logits():
    np.testing.assert_array_equal(_logits(_small(logits_scaling=8.0), TOKENS),
                                  _logits(_small(logits_scaling=1.0), TOKENS) / 8)


def test_without_residual_the_logits_are_the_scaled_embedding_read_out():
    cfg = _small(residual_multiplier=0.0)
    params = build_model(cfg).init(jax.random.key(0))
    table = params["embed"]["table"].astype(jnp.float32)
    h = (table[TOKENS].astype(jnp.bfloat16) * jnp.bfloat16(cfg.embedding_multiplier))
    h = nn.rmsnorm_apply(params["final_norm"], h, eps=cfg.norm_eps)
    want = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32), table) / cfg.logits_scaling
    np.testing.assert_allclose(_logits(cfg, TOKENS)[..., : cfg.vocab],
                               np.asarray(want)[..., : cfg.vocab], atol=2e-2, rtol=2e-2)


def test_embedding_and_residual_multipliers_weigh_the_embedding_against_the_layers():
    """Every layer reads its input through a norm, so scaling both
    multipliers by one factor scales the residual stream and leaves the
    logits; scaling one of them alone moves them."""
    base = _logits(_small(embedding_multiplier=12.0, residual_multiplier=0.22), TOKENS)
    both = _logits(_small(embedding_multiplier=6.0, residual_multiplier=0.11), TOKENS)
    one = _logits(_small(embedding_multiplier=6.0, residual_multiplier=0.22), TOKENS)
    np.testing.assert_allclose(both, base, atol=3e-2, rtol=3e-2)
    assert np.max(np.abs(one - base)) > 10 * np.max(np.abs(both - base))


def _attention_by_hand(cfg, lp, x):
    """softmax(attention_multiplier * q k^T, causal) v W_o, no positions."""
    B, S, _ = x.shape
    hd, G = cfg.resolved_head_dim, cfg.n_heads // cfg.n_kv_heads
    f = lambda w: w.astype(jnp.float32)  # noqa: E731
    xn = nn.rmsnorm_apply(lp["attn_norm"], x.astype(jnp.float32), eps=cfg.norm_eps)
    q = (xn @ f(lp["attn"]["wq"])).reshape(B, S, cfg.n_heads, hd)
    k = jnp.repeat((xn @ f(lp["attn"]["wk"])).reshape(B, S, -1, hd), G, axis=2)
    v = jnp.repeat((xn @ f(lp["attn"]["wv"])).reshape(B, S, -1, hd), G, axis=2)
    s = cfg.attention_multiplier * jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, -1) @ f(lp["attn"]["wo"])


def _attention_params(cfg):
    """The weights of the first attention layer of ``cfg``'s model."""
    params = build_model(cfg).init(jax.random.key(2))
    run = [i for i, (kind, _, _) in enumerate(gh.runs(cfg)) if kind == "attention"][0]
    return jax.tree_util.tree_map(lambda a: a[0], params["layers"][run])


def _attention_out(cfg, lp, x):
    """The attention layer's mixer output, recovered from the layer with
    its MLP and the residual taken away."""
    no_mlp = dict(lp, mlp=jax.tree_util.tree_map(jnp.zeros_like, lp["mlp"]))
    y, _ = gh.attention_layer(cfg, PLAN, x, no_mlp)
    return (y.astype(jnp.float32) - x.astype(jnp.float32)) / cfg.residual_multiplier


@pytest.mark.parametrize("mult", [0.015625, 0.5])
def test_attention_scores_are_scaled_by_attention_multiplier(mult):
    cfg = _small(attention_multiplier=mult, residual_multiplier=1.0)
    lp = _attention_params(cfg)
    x = jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model)).astype(jnp.bfloat16)
    want = np.asarray(_attention_by_hand(cfg, lp, x))
    np.testing.assert_allclose(np.asarray(_attention_out(cfg, lp, x)), want,
                               atol=3e-2 * np.max(np.abs(want)), rtol=0)


def test_nope_attention_ignores_the_order_of_earlier_keys():
    """Without positions, the last query's output depends on the set of
    earlier tokens only: permuting them leaves it to rounding, while
    replacing one of them moves it by far more."""
    perm = jnp.concatenate([jax.random.permutation(jax.random.key(5), 15), jnp.array([15])])
    x = jax.random.normal(jax.random.key(4), (2, 16, 64)).astype(jnp.bfloat16)
    other = x.at[:, 0].set(jax.random.normal(jax.random.key(6), (2, 64)).astype(jnp.bfloat16))
    cfg = _small()
    lp = _attention_params(cfg)
    last = lambda t: _attention_out(cfg, lp, t)[:, -1]  # noqa: E731
    a = last(x)
    gap = lambda b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))  # noqa: E731
    permuted, replaced = gap(last(x[:, perm])), gap(last(other))
    assert permuted < 2e-2 and replaced > 10 * max(permuted, 1e-3), (permuted, replaced)
