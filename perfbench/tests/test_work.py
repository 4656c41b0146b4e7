"""The work counts against the program's own parameter tree and a
brute-force count, at a small size."""

import jax
import numpy as np
import pytest

import tiny
import work


def _params(family, **changes):
    ctx = tiny.context(family)
    conf = dict(ctx.conf, **changes)
    return conf, jax.eval_shape(
        lambda k: ctx.cfgmod.init_weights(conf, 0), jax.random.PRNGKey(0))


def _size(tree, pred):
    return sum(int(np.prod(x.shape)) for path, x in
               jax.tree_util.tree_leaves_with_path(tree) if pred(path))


def _key(path):
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("family", ["attention", "mamba2"])
def test_weight_bytes_match_the_tree(family, tied):
    tie_key = tiny.TIE_KEYS[family]
    conf, p = _params(family, **{tie_key: tied})
    s = work.Shapes.from_config(conf)
    every = _size(p, lambda path: True)
    embed = _size(p, lambda path: _key(path) == "embed")
    # the embedding is gathered a row at a time; a tied head reads the
    # whole table once more
    assert s.weight_bytes_per_step() == 2 * (every - embed
                                             + (embed if tied else 0))


@pytest.mark.parametrize("family", ["attention", "mamba2"])
def test_token_flops_count_every_matrix(family):
    conf, p = _params(family)
    s = work.Shapes.from_config(conf)
    mats = _size(p, lambda path: _key(path).endswith("/w")
                 and _key(path).startswith("stack"))
    extra = 0
    if family == "mamba2":
        conv = 2 * (s.d_inner + 2 * s.d_state) * s.conv_width
        extra = s.layers * (conv + 5 * s.ssm_heads * s.d_state
                            * s.ssm_head_dim)
    assert s.token_flops() == 2 * mats + extra


def test_attention_counts_each_causal_pair_once():
    conf, _ = _params("attention")
    s = work.Shapes.from_config(conf)
    per_pair = s.layers * 4 * s.heads * s.head_dim
    brute = sum(q + 1 for q in range(5, 5 + 7)) * per_pair
    assert s.attn_flops(5, 7) == brute
    assert s.prefill_flops(7) == 7 * s.token_flops() + \
        s.attn_flops(0, 7) + s.head_flops()
    assert s.decode_flops(12) == s.token_flops() + s.attn_flops(11, 1) + \
        s.head_flops()


def test_decode_bytes_grow_with_live_context_only():
    conf, _ = _params("attention")
    s = work.Shapes.from_config(conf)
    row = s.layers * 2 * s.kv_heads * s.head_dim * 2
    assert s.decode_bytes([10, 20]) - s.decode_bytes([10, 19]) == row
    assert s.decode_bytes([]) == s.weight_bytes_per_step()
    conf, _ = _params("mamba2")
    m = work.Shapes.from_config(conf)
    assert m.decode_bytes([5]) == m.decode_bytes([500])
