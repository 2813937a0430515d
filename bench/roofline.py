"""Peaks of the chips and the work a round requires, for roofline shares.

The work is what the algorithm needs, counted from the data's nonzeros and
the filter's budget ``k = rho d``, whatever layout implements it: a stored
nonzero is a float32 value and an int32 index (8 bytes), a float32 vector
entry is 4 bytes, a multiply-add is 2 operations.  The program today stores
``X`` dense and does far more; counting that would let a sparse layout read
over 100%.  Each function below is one layer's term and returns
``(operations, bytes)``.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def least_seconds(ops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: compute or memory bound."""
    p = peak(device_kind)
    return max(ops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])


def local_solve(steps: int, nnz_row: float) -> tuple[float, float]:
    """``steps`` SDCA coordinate steps on sparse rows (core/sdca.py).

    Each step reads its row (8 B a nonzero), the margin's two dots read
    ``w_eff`` and ``v`` at the row's nonzeros (2 x 2 ops, 2 x 4 B), and the
    update adds into ``v`` there (2 ops, 4 B)."""
    return 6.0 * nnz_row * steps, 20.0 * nnz_row * steps


def message_filter(d: int, k: int) -> tuple[float, float]:
    """One reply's top-k filter (core/filter.py, core/compress.py):
    ``dw = residual + v`` over d (read both, 1 op), a selection over d
    (1 op an entry), the new residual written (4d B) and ``k`` (index,
    value) pairs sent."""
    return 2.0 * d, 12.0 * d + 8.0 * k


def server_apply(arrivals: float, workers: int, k: int) -> tuple[float, float]:
    """The server's apply of ``arrivals`` payloads of ``k`` entries
    (engine._server_apply_fused): sum them (1 op, 8 B read each), add into
    ``w_server`` (read and write), add into every worker's catch-up buffer
    (read and write, 8 B each), and deliver each entry once to every
    worker's local model (read the buffer, read and write ``w_local``)."""
    entries = arrivals * k
    ops = entries + 2.0 * workers * entries
    nbytes = 16.0 * entries + 20.0 * workers * entries
    return ops, nbytes


def certificate(nnz_total: float, n: int, d: int) -> tuple[float, float]:
    """One gap certificate (core/objectives.py): ``w(alpha) = X^T alpha``,
    then the margins ``X w`` of ``w(alpha)`` and of the server's ``w``:
    three passes over the nonzeros, each 2 ops and 8 B a nonzero; the two
    margin passes also gather ``w`` (4 B a nonzero); each pass reads or
    writes an n-vector and a d-vector."""
    ops = 6.0 * nnz_total + 4.0 * n + 4.0 * d
    nbytes = 32.0 * nnz_total + 12.0 * n + 12.0 * d
    return ops, nbytes


def _add(*terms):
    return (sum(t[0] for t in terms), sum(t[1] for t in terms))


def _scale(term, factor):
    return term[0] * factor, term[1] * factor


def group_round(*, K: int, n_k: int, d: int, nnz_row: float, B: int, T: int,
                H: int, k: int, eval_every: int) -> tuple[float, float]:
    """One round of ACPD's ``group`` protocol, averaged over its period:
    ``T - 1`` rounds relaunch ``B`` workers and every ``T``-th relaunches
    all ``K``; each relaunch is ``H`` steps and one filtered reply; a
    certificate every ``eval_every`` rounds."""
    relaunches = ((T - 1) * B + K) / T
    worker = _add(local_solve(H, nnz_row), message_filter(d, k))
    n = K * n_k
    return _add(_scale(worker, relaunches),
                server_apply(relaunches, K, k),
                _scale(certificate(nnz_row * n, n, d), 1.0 / eval_every))


def lockstep_round(*, cells: int, K: int, n_k: int, d: int, nnz_row: float,
                   H: int, eval_every: int) -> tuple[float, float]:
    """One round of ``cocoa_plus`` for each of ``cells`` sweep cells: ``K``
    workers of ``H`` steps, then ``w += gamma sum_k v_k`` over the entries
    the workers touched (at most ``min(d, H nnz)`` each: 2 ops, 12 B);
    a certificate every ``eval_every`` rounds."""
    n = K * n_k
    touched = min(d, H * nnz_row)
    aggregate = (2.0 * K * touched, 12.0 * K * touched)
    one = _add(_scale(local_solve(H, nnz_row), K), aggregate,
               _scale(certificate(nnz_row * n, n, d), 1.0 / eval_every))
    return _scale(one, cells)
