"""Operations and bytes ragged paged attention needs, from what it serves.

Required work only. A decoded token at context ``c`` reads the keys and
values of its ``c`` live tokens once in every layer (dead blocks and the pad
of a block or a bucket are not required) and does one score and one value
product against them. A prompt chunk of ``p`` tokens on an empty cache
attends causally: half the square of products, its keys and values read once.
"""


def kv_bytes_per_token(d_model: int, itemsize: int = 2) -> int:
    return 2 * d_model * itemsize  # one key and one value row, all heads


def decode_bytes(context: int, d_model: int, n_layers: int, itemsize: int = 2) -> float:
    return float(n_layers) * context * kv_bytes_per_token(d_model, itemsize)


def decode_flops(context: int, d_model: int, n_layers: int) -> float:
    return float(n_layers) * 4 * d_model * context  # q.K^T and p.V


def prefill_bytes(prompt: int, d_model: int, n_layers: int, itemsize: int = 2) -> float:
    return float(n_layers) * prompt * kv_bytes_per_token(d_model, itemsize)


def prefill_flops(prompt: int, d_model: int, n_layers: int) -> float:
    return float(n_layers) * 4 * d_model * prompt * (prompt + 1) / 2
