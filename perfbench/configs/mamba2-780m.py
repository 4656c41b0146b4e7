"""mamba2-780m: weights from the seed, the plain float32 reference, and
the mapping to the program's config.

The reference is Mamba-2 written out as a recurrence: per block RMSNorm
-> in_proj -> (z, xBC, dt) -> causal depthwise conv + SiLU over xBC ->
per-head selective state update h_t = exp(dt_t A) h_t-1 + B_t (dt_t x_t),
y_t = C_t h_t + D x_t -> RMSNorm(y * SiLU(z)) -> out_proj -> residual;
final RMSNorm and the head tied to the embedding.  It imports nothing
of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import refcommon as R


def program_config(conf: dict):
    from repro.models.config import ArchConfig, SSMConfig

    return ArchConfig(
        name="mamba2-780m", kind="decoder", n_layers=conf["n_layer"],
        d_model=conf["d_model"], n_heads=0, n_kv=0, d_ff=0,
        vocab=padded_vocab(conf), layer_pattern=("ssm",),
        ssm=SSMConfig(d_state=conf["d_state"], expand=conf["expand"],
                      head_dim=conf["headdim"], n_groups=conf["ngroups"],
                      conv_width=conf["d_conv"], chunk=conf["chunk_size"]),
        sub_quadratic=True, norm_eps=conf["norm_epsilon"],
        tie_embeddings=conf["tie_embeddings"])


def padded_vocab(conf: dict) -> int:
    """Rows of the embedding table and of the logits: the tokenizer's
    ids rounded up to `pad_vocab_size_multiple`."""
    m = conf["pad_vocab_size_multiple"]
    return -(-conf["vocab_size"] // m) * m


def _dims(conf):
    d = conf["d_model"]
    d_in = conf["expand"] * d
    heads = d_in // conf["headdim"]
    gn = conf["ngroups"] * conf["d_state"]
    return conf["n_layer"], d, d_in, heads, gn, d_in + 2 * gn


def init_weights(conf: dict, seed: int):
    """bf16 weights from `seed`, made on the device in one jit, with the
    published initialisation's ranges: A = -[1, 16], dt in
    [1e-3, 1e-1] through softplus, D near 1."""
    n, d, d_in, heads, gn, conv_ch = _dims(conf)
    w, v = conf["d_conv"], padded_vocab(conf)
    d_proj = 2 * d_in + 2 * gn + heads

    @jax.jit
    def make(key):
        k = iter(jax.random.split(key, 16))
        lin = lambda shape: R.normal(next(k), shape, shape[-2] ** -0.5)
        vec = lambda shape, s: R.normal(next(k), shape, s)
        u = lambda shape, lo, hi: jax.random.uniform(next(k), shape,
                                                     jnp.float32, lo, hi)
        dt = jnp.exp(u((n, heads), math.log(1e-3), math.log(1e-1)))
        return {
            "embed": R.normal(next(k), (v, d), d ** -0.5),
            "stack": {"b0": {
                "norm1": vec((n, d), 0.1),
                "ssm": {
                    "in_proj": {"w": lin((n, d, d_proj))},
                    "conv_w": R.normal(next(k), (n, w, conv_ch), w ** -0.5),
                    "conv_b": vec((n, conv_ch), 0.02),
                    "A_log": jnp.log(u((n, heads), 1.0, 16.0)).astype(
                        jnp.bfloat16),
                    "D": (1.0 + u((n, heads), -0.1, 0.1)).astype(
                        jnp.bfloat16),
                    "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(
                        jnp.bfloat16),
                    "norm": vec((n, d_in), 0.1),
                    "out_proj": {"w": lin((n, d_in, d))}}}},
            "tail": [],
            "final_norm": vec((d,), 0.1),
        } | ({} if conf["tie_embeddings"] else
             {"lm_head": R.normal(next(k), (d, v), d ** -0.5)})

    return make(R.key_from_seed(seed))


def logits_rows(conf: dict, params, tokens, rows, mm):
    """float32 logits at `rows` of the causal forward over `tokens` (S,)."""
    n, d, d_in, heads, gn, conv_ch = _dims(conf)
    eps, p_dim, n_st = conf["norm_epsilon"], conf["headdim"], conf["d_state"]
    ng, w = conf["ngroups"], conf["d_conv"]
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)

    def block(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        q = p["ssm"]
        u = mm(R.rms(x, p["norm1"], eps), q["in_proj"]["w"])
        z, xbc, dt = jnp.split(u, [d_in, 2 * d_in + 2 * gn], axis=-1)
        xp = jnp.pad(xbc, ((w - 1, 0), (0, 0)))
        xbc = sum(xp[i:i + s] * q["conv_w"][i] for i in range(w))
        xbc = jax.nn.silu(xbc + q["conv_b"])
        xs, bm, cm = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
        xs = xs.reshape(s, heads, p_dim)
        rep = heads // ng
        bm = jnp.repeat(bm.reshape(s, ng, n_st), rep, axis=1)
        cm = jnp.repeat(cm.reshape(s, ng, n_st), rep, axis=1)
        dt = jax.nn.softplus(dt + q["dt_bias"])              # (S, H)
        a = -jnp.exp(q["A_log"])

        def step(h, t):
            xt, bt, ct, dtt = t
            h = h * jnp.exp(dtt * a)[:, None, None] \
                + bt[:, :, None] * (xt * dtt[:, None])[:, None, :]
            y = jnp.einsum("hn,hnp->hp", ct, h, precision=R.HIGHEST)
            return h, y + q["D"][:, None] * xt

        h0 = jnp.zeros((heads, n_st, p_dim), jnp.float32)
        _, y = jax.lax.scan(step, h0, (xs, bm, cm, dt))
        y = R.rms(y.reshape(s, d_in) * jax.nn.silu(z), q["norm"], eps)
        return x + mm(y, q["out_proj"]["w"]), None

    x, _ = jax.lax.scan(block, x, params["stack"]["b0"])
    xr = R.rms(x[rows], params["final_norm"].astype(jnp.float32), eps)
    head = (params["embed"].T if conf["tie_embeddings"]
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
