"""Operations and bytes the algorithm needs, from shapes, and the chip's
peaks (``peaks.json``, keyed by ``device_kind``).

``client_eval_work`` counts one lane-round of the client evaluation: the
round's ``(K, W)`` window of expert predictions and its ``W`` targets, the
lane's ``K`` weights and transmit flags and two cursor scalars in; the
mixture, the two ensemble scalars, the ``K`` per-expert losses and (for
FedBoost) the ``K``-wide gradient out.  It does not count the rest of the
``(K, n_stream + W)`` stream, which no round needs, so the share reads the
same work whatever memory the implementation keeps the stream in.
Operations are the float adds, multiplies, divides, compares and
exponentials of the formulas, one each.
"""

from __future__ import annotations

import json
from pathlib import Path

F32 = 4


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table['devices'])})") from None


def client_eval_work(K: int, W: int, with_grad: bool) -> tuple:
    """``(ops, bytes)`` of one lane-round of the client evaluation.

    EFL-FG (``with_grad`` off) mixes by eq. (5), a softmax of the log
    weights over the transmit set: max, subtract, exp, sum, divide per
    expert (5K).  FedBoost mixes linearly, w / sum(w) over the set (2K),
    and adds the gradient: the residual (W), its product with every
    prediction and the sum (2KW), the 2/n scale (K)."""
    mix = 2 * K if with_grad else 5 * K
    losses = 5 * K * W          # (p - y), square, / scale, min 1, sum
    yhat = 2 * K * W            # the mixture's matvec
    ens = 6 * W + 1             # (yhat - y), square, sum, / n, / scale, min
    grad = 2 * K * W + K + W if with_grad else 0
    ops = mix + losses + yhat + ens + grad
    read = (K * W + W + 2 * K + 2) * F32
    write = (K + 2 + K + (K if with_grad else 0)) * F32
    return ops, read + write


def least_time_s(ops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
