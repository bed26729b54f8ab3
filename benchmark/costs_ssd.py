"""Operations and bytes of a Mamba-2 mixer's state-space core and of
two-matrix (squared-ReLU) experts, from shapes and counts alone
(``costs.py``'s rules: the products' 2 x multiply-accumulates, forward and
two gradient products; recomputed work and masked entries are not counted).
``costs.least_seconds`` turns a pair into the roofline's time. Also which
decoder blocks a configuration's ``pattern`` makes Mamba-2 mixers or
attention, and the scopes the core is traced under."""
from __future__ import annotations

MAMBA, ATTENTION = "M", "*"
#: the core: the taps, the steps, the scan, the skip, the gate and the norm
#: (``DecoderBlock._mamba_part``)
SCOPE = r"/attn/ssd(/|$)"
#: the chunked scan alone (``ops/ssd.py``), inside the map over the groups
SCAN_SCOPE = r"/attn/ssd/(.*/)?scan(/|$)"
#: tokens the program ran through each scan, by block
TOKENS = "dl4j_ssm_tokens_total"


def chunk_pairs(seq: int, chunk: int) -> int:
    """(row, column) pairs at or below the diagonal of the chunked form's
    ``[chunk, chunk]`` blocks over ``seq`` tokens (the last one partial)."""
    whole, rest = divmod(seq, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def ssd_core(tokens: float, seq: int, heads: int, head_dim: int, groups: int,
             state: int, chunk: int, itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of ``tokens`` (sequences of ``seq``) through one
    Mamba-2 mixer's core, forward and backward. Operations: the chunked
    form's four products at ``chunk``: ``C B^T`` (``groups``, ``state``
    deep) and its mix with ``dt x`` (``heads``, ``head_dim`` wide) at the
    causal pairs of each chunk, each chunk's state and the carried state's
    part (``heads x head_dim x state`` a token each); each with two more for
    its gradients. Bytes: forward reads ``z``, ``xBC`` and ``dt`` (``W_in``'s
    output, ``2 d + 2 G N + H`` a token) and writes the gated, normed ``y``
    (``d``); backward reads ``y``'s gradient and the same three and writes
    their gradients. The taps, the state and the chunks' intermediates stay
    inside and are not counted."""
    d = heads * head_dim
    per_seq = chunk_pairs(seq, chunk) * (groups * state + heads * head_dim)
    macs = tokens / seq * per_seq + tokens * 2 * heads * head_dim * state
    width_in = 2 * d + 2 * groups * state + heads
    return 3 * 2.0 * macs, tokens * (3 * width_in + 2 * d) * itemsize


def relu2_experts(rows: float, width: int, hidden: int, experts: int,
                  itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of ``rows`` (token, choice) pairs through a
    squared-ReLU feed-forward over ``experts`` held experts, forward and
    backward: two grouped products ``rows x width x hidden``, each with two
    more for its gradients (``costs.grouped_ffn``'s rule with two matrices
    for three). Bytes: every product reads its row operand and writes its
    result once, and reads or writes the experts' weights once."""
    products = 2 * 3
    flops = products * 2.0 * rows * width * hidden
    per_product = rows * (width + hidden) + experts * width * hidden
    return flops, products * per_product * itemsize


def blocks_of(kwargs: dict, letter: str) -> list:
    """The decoder blocks (0-based) whose letter of ``pattern`` is
    ``letter``; empty where the configuration has no ``pattern`` with a
    Mamba-2 mixer (a configuration of another family)."""
    pattern = kwargs.get("pattern") or ""
    if MAMBA not in pattern:
        return []
    return [i for i, k in enumerate(pattern) if k == letter]
