"""Operations and parameters from shapes, for the xLSTM stack as the program
runs it: the work model a configuration names under ``"work"`` (one file
per model family under ``work/``, found by that name).

Each function takes a config file's ``model`` dict. Counts are of
multiply-adds as two operations, for what the program computes: the
matrix products of every block, the mLSTM recurrence in its chunked form
(intra-chunk products over the whole chunk, masked half included), the
sLSTM recurrent product, and the output head. Left out: the embedding lookup, elementwise work, and
recomputation (a step with block remat runs the forward twice; it counts
once). A training token costs its forward three times over (forward,
and a backward of twice the forward).
"""
from __future__ import annotations

MLSTM_CHUNK = 256    # chunk of the program's chunked linear scan


def block_kinds(m: dict) -> list:
    """Per-layer block kinds, in order, as the program builds them."""
    fam, n = m["family"], m["num_layers"]
    if fam == "ssm":
        every = m.get("slstm_every", 0)
        return ["slstm" if every and (i + 1) % every == 0 else "mlstm"
                for i in range(n)]
    raise ValueError(f"no work model for family {fam!r}")


def _mlstm_dims(m: dict):
    inner = m.get("ssm_expand", 2) * m["d_model"]
    nh = m["num_heads"]
    return inner, nh, inner // nh


def matmul_weights(m: dict, kind: str) -> int:
    """Weights one token multiplies through in a block of ``kind``."""
    d = m["d_model"]
    if kind == "mlstm":
        inner, nh, _ = _mlstm_dims(m)
        return d * 2 * inner + 3 * inner * inner + 2 * inner * nh + inner * d
    if kind == "slstm":
        nh = m["num_heads"]
        hs = d // nh
        return d * 4 * d + nh * hs * 4 * hs + d * d
    raise ValueError(kind)


def param_count(m: dict) -> int:
    """Parameters as implemented: embedding, head, blocks, norms."""
    d = m["d_model"]
    total = m["vocab_size"] * d * (1 if m.get("tie_embeddings") else 2)
    for kind in block_kinds(m):
        if kind == "mlstm":
            total += matmul_weights(m, kind) + d
        else:
            total += matmul_weights(m, kind) + 4 * d + d
    return total + d


def _mixer_flops_per_token(m: dict, kind: str, seq: int) -> float:
    """Sequence-mixing operations for one token of a block."""
    if kind == "mlstm":
        _, nh, mhd = _mlstm_dims(m)
        c = MLSTM_CHUNK if seq % MLSTM_CHUNK == 0 else seq
        # intra-chunk q.k and (gate*qk).v_aug, then q.H and the state update
        return nh * (2.0 * c * mhd + 2.0 * c * (mhd + 1)
                     + 4.0 * mhd * (mhd + 1))
    return 0.0                                  # slstm: counted in weights


def forward_flops_per_token(m: dict, seq: int) -> float:
    """Mean forward operations per token of a causal sequence of ``seq``."""
    total = 2.0 * m["d_model"] * m["vocab_size"]
    for kind in block_kinds(m):
        total += 2.0 * matmul_weights(m, kind)
        total += _mixer_flops_per_token(m, kind, seq)
    return total


def train_flops_per_token(m: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq)
