"""What the algorithm needs, counted from the cell's shapes.

These counts are the yardstick for every roofline share and MFU: they
count the real prompt tokens and each slot's live context, never the
bucketed width, the unused pool rows or `max_seq`, so a program that
does less padded work reads closer to 100 % and never above it.

`Shapes` is read from a configuration file (`perfbench/configs/*.json`);
the two families here are a decoder of full-attention blocks with a
gated MLP, and a Mamba-2 stack of SSD blocks.
"""

from __future__ import annotations

import dataclasses

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shapes:
    family: str          # "attention" | "mamba2"
    layers: int
    d_model: int
    vocab: int           # rows of the embedding table and the logits
    token_ids: int       # ids the tokenizer gives, at most `vocab`
    # attention
    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    qkv_bias: bool = False
    # mamba2
    d_inner: int = 0
    d_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    n_groups: int = 0
    conv_width: int = 0

    @classmethod
    def from_config(cls, conf: dict) -> "Shapes":
        if conf["family"] == "attention":
            return cls("attention", conf["num_hidden_layers"],
                       conf["hidden_size"], conf["vocab_size"],
                       conf["vocab_size"],
                       heads=conf["num_attention_heads"],
                       kv_heads=conf["num_key_value_heads"],
                       head_dim=conf["head_dim"],
                       d_ff=conf["intermediate_size"],
                       qkv_bias=conf["qkv_bias"])
        if conf["family"] == "mamba2":
            d_in = conf["expand"] * conf["d_model"]
            m = conf.get("pad_vocab_size_multiple", 1)
            return cls("mamba2", conf["n_layer"], conf["d_model"],
                       -(-conf["vocab_size"] // m) * m, conf["vocab_size"],
                       d_inner=d_in,
                       d_state=conf["d_state"],
                       ssm_heads=d_in // conf["headdim"],
                       ssm_head_dim=conf["headdim"],
                       n_groups=conf["ngroups"], conv_width=conf["d_conv"])
        raise ValueError(f"unknown family {conf['family']!r}")

    # -- parameters ------------------------------------------------------

    def layer_matmul_params(self) -> int:
        """Weights one token multiplies by in one block."""
        d = self.d_model
        if self.family == "attention":
            qkv = d * self.head_dim * (self.heads + 2 * self.kv_heads)
            return qkv + self.heads * self.head_dim * d + 3 * d * self.d_ff
        d_proj = 2 * self.d_inner + 2 * self.n_groups * self.d_state \
            + self.ssm_heads
        return d * d_proj + self.d_inner * d

    def layer_param_bytes(self) -> int:
        """Bytes of one block's weights as served (bf16), small vectors
        included."""
        d = self.d_model
        if self.family == "attention":
            vec = 2 * d + (self.head_dim * (self.heads + 2 * self.kv_heads)
                           if self.qkv_bias else 0)
        else:
            conv_ch = self.d_inner + 2 * self.n_groups * self.d_state
            vec = (d + self.d_inner + conv_ch * (self.conv_width + 1)
                   + 3 * self.ssm_heads)
        return BF16 * (self.layer_matmul_params() + vec)

    # -- flops -------------------------------------------------------------

    def token_flops(self) -> int:
        """Matmul FLOPs of one token through every block (no head)."""
        per = 2 * self.layer_matmul_params()
        if self.family == "mamba2":
            conv_ch = self.d_inner + 2 * self.n_groups * self.d_state
            # depthwise conv, and the SSD recurrence as a recurrence:
            # decay and outer-product update of the (N, P) state, then
            # the C-contraction that reads it
            per += 2 * conv_ch * self.conv_width
            per += 5 * self.ssm_heads * self.d_state * self.ssm_head_dim
        return self.layers * per

    def head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def attn_flops(self, q_pos_start: int, n_q: int) -> int:
        """Score and value FLOPs of `n_q` causal queries at positions
        q_pos_start .. q_pos_start+n_q-1, each over its own prefix."""
        if self.family != "attention":
            return 0
        keys = n_q * q_pos_start + n_q * (n_q + 1) // 2
        return self.layers * 4 * self.heads * self.head_dim * keys

    def prefill_flops(self, prompt_len: int) -> int:
        """One prompt prefilled: every token through every block, causal
        attention, and the head on the last row only."""
        return (prompt_len * self.token_flops()
                + self.attn_flops(0, prompt_len) + self.head_flops())

    def decode_flops(self, ctx: int) -> int:
        """One token decoded at position ctx-1 (ctx rows live after the
        write), head included."""
        return (self.token_flops() + self.attn_flops(ctx - 1, 1)
                + self.head_flops())

    # -- bytes ---------------------------------------------------------------

    def weight_bytes_per_step(self) -> int:
        """Weights a decode step must read once: every block and the
        head; the embedding is gathered, one row per active slot."""
        return (self.layers * self.layer_param_bytes()
                + BF16 * (self.d_model * self.vocab + self.d_model))

    def state_bytes(self) -> int:
        """Mamba-2: one slot's SSM state (f32) and conv tail (bf16) over
        every block."""
        conv_ch = self.d_inner + 2 * self.n_groups * self.d_state
        state = F32 * self.ssm_heads * self.d_state * self.ssm_head_dim
        conv = BF16 * (self.conv_width - 1) * conv_ch
        return self.layers * (state + conv)

    def slot_bytes(self, ctx: int) -> int:
        """Per active slot per decode step: the live cache it reads and
        the row or state it writes, its embedding row and its logits
        row (bf16)."""
        own = BF16 * (self.d_model + self.vocab)
        if self.family == "attention":
            row = 2 * self.kv_heads * self.head_dim * BF16  # k and v
            return own + self.layers * row * (ctx + 1)
        return own + 2 * self.state_bytes()

    def prefill_bytes(self, prompt_lens: list[int]) -> int:
        """One prefill call: weights read once, each prompt's cache
        rows (or final state) written."""
        if self.family == "attention":
            per_tok = self.layers * 2 * self.kv_heads * self.head_dim * BF16
            written = per_tok * sum(prompt_lens)
        else:
            written = len(prompt_lens) * self.state_bytes()
        return self.weight_bytes_per_step() + written

    def decode_bytes(self, ctxs: list[int]) -> int:
        """One decode step over the active slots with these live
        context lengths."""
        return self.weight_bytes_per_step() + sum(
            self.slot_bytes(c) for c in ctxs)
