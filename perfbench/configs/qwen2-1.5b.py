"""qwen2-1.5b: weights from the seed, the plain float32 reference, and
the mapping to the program's config.

The reference is Qwen2's forward pass written out: token embedding,
28 blocks of RMSNorm -> GQA attention (biased q/k/v, rotary, causal
softmax) -> residual -> RMSNorm -> SwiGLU MLP -> residual, final
RMSNorm, the head tied to the embedding.  It imports nothing of the
program; it reads the benchmark's own weights, in the layout the program
is handed them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import refcommon as R


def program_config(conf: dict):
    from repro.models.config import ArchConfig

    return ArchConfig(
        name="qwen2-1.5b", kind="decoder",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], qkv_bias=conf["qkv_bias"],
        head_dim=conf["head_dim"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"])


def _dims(conf):
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["intermediate_size"], conf["vocab_size"])


def init_weights(conf: dict, seed: int):
    """bf16 weights from `seed`, made on the device in one jit.  Matmul
    weights are N(0, 1/fan_in); biases N(0, 0.02^2); norm offsets
    N(0, 0.1^2), so every parameter is exercised."""
    n, d, nh, nkv, hd, f, v = _dims(conf)

    @jax.jit
    def make(key):
        k = iter(jax.random.split(key, 16))
        lin = lambda shape: R.normal(next(k), shape, shape[-2] ** -0.5)
        vec = lambda shape, s: R.normal(next(k), shape, s)
        return {
            "embed": R.normal(next(k), (v, d), d ** -0.5),
            "stack": {"b0": {
                "norm1": vec((n, d), 0.1),
                "attn": {"wq": {"w": lin((n, d, nh * hd)),
                                "b": vec((n, nh * hd), 0.02)},
                         "wk": {"w": lin((n, d, nkv * hd)),
                                "b": vec((n, nkv * hd), 0.02)},
                         "wv": {"w": lin((n, d, nkv * hd)),
                                "b": vec((n, nkv * hd), 0.02)},
                         "wo": {"w": lin((n, nh * hd, d))}},
                "norm2": vec((n, d), 0.1),
                "mlp": {"wi": {"w": lin((n, d, f))},
                        "wg": {"w": lin((n, d, f))},
                        "wo": {"w": lin((n, f, d))}}}},
            "tail": [],
            "final_norm": vec((d,), 0.1),
        } | ({} if conf["tie_word_embeddings"] else
             {"lm_head": R.normal(next(k), (d, v), d ** -0.5)})

    return make(R.key_from_seed(seed))


def _rope(x, theta):
    """x (S, H, D): rotate (even, odd) lane pairs by position."""
    s, _, dd = x.shape
    freq = theta ** (-jnp.arange(0, dd, 2, dtype=jnp.float32) / dd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def logits_rows(conf: dict, params, tokens, rows, mm):
    """float32 logits at `rows` of the causal forward over `tokens` (S,)."""
    n, d, nh, nkv, hd, f, v = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    s = tokens.shape[0]
    g = nh // nkv
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = params["embed"][tokens].astype(jnp.float32)

    def block(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        a = p["attn"]
        h = R.rms(x, p["norm1"], eps)
        q = (mm(h, a["wq"]["w"]) + a["wq"]["b"]).reshape(s, nh, hd)
        k = (mm(h, a["wk"]["w"]) + a["wk"]["b"]).reshape(s, nkv, hd)
        vv = (mm(h, a["wv"]["w"]) + a["wv"]["b"]).reshape(s, nkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(s, nkv, g, hd)
        sc = jnp.einsum("qkgd,skd->kgqs", q, k,
                        precision=R.HIGHEST) / jnp.sqrt(jnp.float32(hd))
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", pr, vv, precision=R.HIGHEST)
        x = x + mm(o.reshape(s, nh * hd), a["wo"]["w"])
        m = p["mlp"]
        h = R.rms(x, p["norm2"], eps)
        x = x + mm(jax.nn.silu(mm(h, m["wg"]["w"])) * mm(h, m["wi"]["w"]),
                   m["wo"]["w"])
        return x, None

    x, _ = jax.lax.scan(block, x, params["stack"]["b0"])
    xr = R.rms(x[rows], params["final_norm"].astype(jnp.float32), eps)
    head = (params["embed"].T if conf["tie_word_embeddings"]
            else params["lm_head"])
    return mm(xr, head.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps(conf_items, params, tokens, rows, served, control):
    return R.gaps(logits_rows, dict(conf_items), params, tokens, rows,
                  served, control)


def gaps(conf: dict, params, tokens, rows, served, *, control: bool):
    """Widest logit gaps of the served tokens (and, with `control`, of
    the precision controls' first choices) at `rows` of `tokens`."""
    items = tuple((k, v) for k, v in sorted(conf.items())
                  if isinstance(v, (int, float, str, bool)))
    return _gaps(items, params, tokens, rows, served, control)
