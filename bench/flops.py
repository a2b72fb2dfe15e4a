"""Operations and bytes of the served model, computed from its shapes.

Every count here is the least the algorithm needs, not what the program
happens to do: no padding rows, attention over the positions a row really
attends to, each weight read once per executed program.  Matmuls count
2 operations per multiply-accumulate.  Weights, activations and the KV
cache are bf16 (2 bytes) unless a caller says otherwise.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder with GQA attention and a SwiGLU MLP."""

    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qk_norm: bool = False

    @classmethod
    def from_spec(cls, spec: dict) -> "Dims":
        """From a configuration file's published keys (as run)."""
        return cls(
            d_model=spec["hidden_size"],
            n_layers=spec["num_hidden_layers"],
            n_heads=spec["num_attention_heads"],
            n_kv_heads=spec["num_key_value_heads"],
            head_dim=spec.get("head_dim")
            or spec["hidden_size"] // spec["num_attention_heads"],
            d_ff=spec["intermediate_size"],
            vocab=spec["vocab_size"],
            qk_norm=bool(spec.get("qk_norm", False)),
        )

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def layer_params(self) -> int:
        """Matmul weights of one layer (attention projections + MLP)."""
        d = self.d_model
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d \
            + 3 * d * self.d_ff

    def layer_weight_bytes(self, nbytes: int = 2) -> int:
        """Everything one layer reads of its weights, norms included."""
        norms = 2 * self.d_model + (2 * self.head_dim if self.qk_norm else 0)
        return (self.layer_params() + norms) * nbytes


def token_layer_flops(dims: Dims, context: int) -> int:
    """One token through one layer attending to ``context`` positions."""
    return 2 * dims.layer_params() + 4 * dims.q_dim * context


def head_flops(dims: Dims) -> int:
    """One token through a norm + unembedding head."""
    return 2 * dims.d_model * dims.vocab


def prefill_flops(dims: Dims, prompt_len: int) -> int:
    """One prompt through every layer (causal attention) plus the main head
    at its last position, which is all the admission prefill computes."""
    p = prompt_len
    attn = 4 * dims.q_dim * p * (p + 1) // 2
    return dims.n_layers * (2 * dims.layer_params() * p + attn) \
        + head_flops(dims)


def kv_bytes(dims: Dims, layers: int, positions: int, nbytes: int = 2) -> int:
    """K and V of ``positions`` cached tokens over ``layers`` layers."""
    return 2 * layers * positions * dims.kv_dim * nbytes


def head_weight_bytes(dims: Dims, nbytes: int = 2) -> int:
    return (dims.d_model * dims.vocab + dims.d_model) * nbytes


def flash_decode_cost(contexts, n_heads: int, n_kv_heads: int,
                      head_dim: int, nbytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one single-token decode-attention call in one
    layer, one query row per entry of ``contexts`` (the positions that row
    attends to): QK and PV contractions, and each row's K, V and slot
    positions (int32) of those positions, its q and its output."""
    ctx = sum(int(c) for c in contexts)
    rows = len(contexts)
    flops = 4 * n_heads * head_dim * ctx
    nb = (2 * ctx * n_kv_heads * head_dim * nbytes + 4 * ctx
          + 2 * rows * n_heads * head_dim * nbytes)
    return flops, nb


def entropy_exit_cost(heads: int, rows: int, vocab: int,
                      nbytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of the fused entropy / exit flag / argmax pass
    over (heads, rows, vocab) logits: max, subtract, exp, and the two
    accumulations (sum of p and of p * logit) per element, reading the
    logits once and writing three (heads, rows) results."""
    elems = heads * rows * vocab
    return 5 * elems, elems * nbytes + 3 * 4 * heads * rows


def least_seconds(flops: int, nbytes: int, peaks: dict) -> tuple[float, str]:
    """The roofline time of (flops, bytes) and which of the two bounds it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
