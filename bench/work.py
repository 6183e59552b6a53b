"""The work a step REQUIRES, from the configuration's shapes and the live
token counts — never from what the current implementation moves. A
change that stops gathering the whole page pool, or keeps migration off
the host, then reads as a higher share of the roofline; one that does
more work than required does not.

All counts are for the configuration file's model (decoder-only, GQA,
SwiGLU), in the dtype it is served in.
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


class Shapes:
    def __init__(self, cfg: dict):
        self.L = int(cfg["num_hidden_layers"])
        self.D = int(cfg["hidden_size"])
        self.F = int(cfg["intermediate_size"])
        self.H = int(cfg["num_attention_heads"])
        self.KVH = int(cfg["num_key_value_heads"])
        self.hd = int(cfg["head_dim"])
        self.V = int(cfg["vocab_size"])
        self.tied = bool(cfg["tie_word_embeddings"])
        self.bytes = DTYPE_BYTES[cfg["torch_dtype"]]
        self.page_tokens = int(cfg["deployment"]["page_tokens"])

    # -- parameters --------------------------------------------------------
    @property
    def layer_params(self) -> int:
        """Matrix parameters of all layers (norm scales excluded)."""
        D, F, H, KVH, hd = self.D, self.F, self.H, self.KVH, self.hd
        return self.L * (D * H * hd + 2 * D * KVH * hd + H * hd * D
                         + 3 * D * F)

    @property
    def head_params(self) -> int:
        return self.V * self.D

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every layer."""
        return 2 * self.L * self.KVH * self.hd * self.bytes

    @property
    def page_record_bytes(self) -> int:
        """One page of one cache leaf (K or V): the unit of migration."""
        return self.L * self.page_tokens * self.KVH * self.hd * self.bytes

    # -- prefill -----------------------------------------------------------
    def prefill_flops(self, plen: int) -> float:
        """One prompt of `plen` tokens, at its true length: every layer's
        projections for each token, causal attention (QK^T and PV over
        the tokens at or before each position), and the output head for
        the last position only."""
        attn = 4 * self.L * self.H * self.hd * plen * (plen + 1) / 2
        return 2.0 * self.layer_params * plen + attn \
            + 2.0 * self.head_params

    # -- decode ------------------------------------------------------------
    def decode_flops(self, ctx_lens) -> float:
        """Decode tokens, each attending over `ctx` tokens (its own
        included): projections, attention and the output head."""
        ctx = np.asarray(ctx_lens, np.float64)
        per = 2.0 * (self.layer_params + self.head_params)
        return float(ctx.size * per
                     + 4 * self.L * self.H * self.hd * ctx.sum())

    def decode_weight_bytes(self, slots: int) -> float:
        """Weights one batched decode step must read: every layer, the
        output head once, and the embedding rows of its `slots` tokens
        (the whole table when it is tied, since the head reads it)."""
        rows = 0 if self.tied else slots * self.D
        return float((self.layer_params + self.head_params + rows)
                     * self.bytes)

    def decode_kv_bytes(self, ctx_lens) -> float:
        """KV rows decode tokens must touch: read each token's live
        context (its own row excluded) and write its one new row."""
        ctx = np.asarray(ctx_lens, np.float64)
        return float(ctx.sum() * self.kv_bytes_per_token)

    # -- migration ---------------------------------------------------------
    def migrated_bytes(self, plen: int) -> int:
        """KV pages a prompt of `plen` tokens migrates: its pages, K and
        V each."""
        pages = -(-plen // self.page_tokens)
        return 2 * pages * self.page_record_bytes
