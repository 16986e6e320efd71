"""Work a dense GQA decoder needs, counted from its shapes.

These are the operations and bytes the mathematics asks for, not what
a given program does: a step that reads the whole KV cache where only
the valid entries are needed, or unembeds a position whose logits
nobody uses, does more than is counted here.  So a program that stops
wasting work moves the shares computed from these counts, and the
counts themselves stay put.

``model`` is the ``"model"`` block of a configuration file: the field
names of the program's ``ModelConfig`` (``n_layers``, ``d_model``,
``n_heads``, ``n_kv_heads``, ``d_ff``, ``vocab_size``, ``head_dim``,
``tie_embeddings``, ``qkv_bias``).  The FFN is SwiGLU (three matrices).
"""
from __future__ import annotations

from dataclasses import dataclass

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int
    tie_embeddings: bool
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, model: dict, dtype: str) -> "Shapes":
        hd = model.get("head_dim") or model["d_model"] // model["n_heads"]
        return cls(model["n_layers"], model["d_model"], model["n_heads"],
                   model["n_kv_heads"], model["d_ff"], model["vocab_size"],
                   hd, bool(model.get("tie_embeddings", False)),
                   bool(model.get("qkv_bias", False)), DTYPE_BYTES[dtype])

    # -- parameters --------------------------------------------------- #
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        return attn + 3 * d * self.d_ff

    def layer_params(self) -> int:
        bias = ((self.n_heads + 2 * self.n_kv_heads) * self.head_dim
                if self.qkv_bias else 0)
        return self.layer_matmul_params() + bias + 2 * self.d_model

    def table_params(self) -> int:
        return self.vocab_size * self.d_model

    def total_params(self) -> int:
        tables = self.table_params() * (1 if self.tie_embeddings else 2)
        return (tables + self.n_layers * self.layer_params()
                + self.d_model)

    def param_bytes(self) -> int:
        return self.total_params() * self.dtype_bytes

    def kv_bytes_per_token(self) -> int:
        return (self.n_layers * 2 * self.n_kv_heads * self.head_dim
                * self.dtype_bytes)

    # -- one decode step ---------------------------------------------- #
    def step_flops(self, n_tokens: int, sum_ctx: int, n_logits: int) -> int:
        """``n_tokens`` tokens, one per active slot, attending over
        ``sum_ctx`` valid cache entries in all, of which ``n_logits``
        positions need logits."""
        matmul = 2 * n_tokens * self.n_layers * self.layer_matmul_params()
        attn = 4 * self.n_layers * self.n_heads * self.head_dim * sum_ctx
        unembed = 2 * n_logits * self.d_model * self.vocab_size
        return matmul + attn + unembed

    def step_bytes(self, n_tokens: int, sum_ctx: int, n_logits: int) -> int:
        """Weights read once (of the embedding table only the rows of
        the step's tokens), the valid KV entries read and the new ones
        written.  The unembedding table is read only where some
        position needs logits."""
        if n_tokens == 0:
            return 0
        b = self.dtype_bytes
        weights = (self.n_layers * self.layer_params() + self.d_model) * b
        rows = n_tokens * self.d_model * b
        table = self.table_params() * b if n_logits else 0
        kv = (sum_ctx + n_tokens) * self.kv_bytes_per_token()
        return weights + rows + table + kv


def least_seconds(flops: float, nbytes: float, chips: int,
                  peak: dict) -> tuple[float, str]:
    """The least time ``chips`` chips need for the work, and which of
    the two bounds sets it."""
    t_flops = flops / (chips * peak["bf16_flops_per_s"])
    t_bytes = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
