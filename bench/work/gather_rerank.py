"""Work that a pooled exact rerank needs, whatever implements it.

A call reranks, for each of ``Q`` queries, its pool of candidate ids under
the exact squared L2 distance and keeps the best ``k``.  What the operation
needs is to read each live candidate's row once (``valid`` rows of ``D``
float32), the queries and the pool ids, to write ``Q x k`` distances and ids,
and ``2 D + 3`` operations per live candidate (the cross term and the
norms).  A kernel that streams the whole shard to find ``valid`` rows does
more than this, so its share of the roofline is low; a true row gather reads
closer to it.  The shard size ``N`` and the tiles do not enter.

``call`` is what ``layers.Capture.record_kernel`` records: ``Q``, ``D``,
``P`` (pool slots per query), ``valid`` (live slots in all), ``k``.
"""

ITEM = 4  # float32 / int32 bytes


def work(call: dict) -> tuple:
    """(operations, bytes) of one call; the operations are float32
    multiply-adds, bounded against the bf16 peak (``peak``)."""
    q, d, p, valid, k = (int(call[x]) for x in ("Q", "D", "P", "valid", "k"))
    ops = valid * (2 * d + 3)
    nbytes = ITEM * (valid * d + q * d + q * p + 2 * q * k)
    return float(ops), float(nbytes)


def peak(call: dict) -> str:
    return "bf16_flops_per_s"
