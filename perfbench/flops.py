"""Operations a GPT-2-shaped decoder needs, from its shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Convention (PaLM appendix B): a matmul parameter costs 2 FLOPs
per token forward and 4 backward; the output head counts once (it is tied to
the embedding, whose lookup is not a matmul); attention scores and values
cost 12 * layers * d_model * seq per token forward + backward with the full
sequence counted; recomputation (remat) is not counted; biases, norms and
the activation are not counted.
"""

from __future__ import annotations


def param_count(config: dict) -> int:
    """Parameters of the published model (head tied to the embedding)."""
    d, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * d
    per_layer = (2 * d                      # ln_1
                 + d * 3 * d + 3 * d        # c_attn
                 + d * d + d                # attn c_proj
                 + 2 * d                    # ln_2
                 + d * inner + inner        # c_fc
                 + inner * d + d)           # mlp c_proj
    return (config["vocab_size"] * d + config["n_positions"] * d
            + layers * per_layer + 2 * d)


def matmul_params(config: dict) -> int:
    """Weights that meet every token in a matmul: the blocks' four
    projections and the head, once."""
    d, layers = config["n_embd"], config["n_layer"]
    inner = config.get("n_inner") or 4 * d
    return layers * (4 * d * d + 2 * d * inner) + config["vocab_size"] * d


def train_flops_per_token(config: dict, seq_len: int) -> float:
    d, layers = config["n_embd"], config["n_layer"]
    return 6.0 * matmul_params(config) + 12.0 * layers * d * seq_len


def mfu_pct(config: dict, seq_len: int, tokens_per_s: float, chips: int,
            peak_flops: float) -> float:
    return (100.0 * train_flops_per_token(config, seq_len) * tokens_per_s
            / (chips * peak_flops))


def kv_bytes_per_position(config: dict, dtype_bytes: int = 2) -> int:
    """K and V of one cached position over all layers."""
    return 2 * config["n_layer"] * config["n_embd"] * dtype_bytes
