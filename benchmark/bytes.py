"""Bytes a request has to move through HBM, computed from its shape: the
numerator of a roofline share.  Kept with the benchmark so no PR that
claims a gain can change it.

A request over `points` stored points reads each point's timestamp and
value once (int64 + float64 = 16 B) and writes its answer (one float64
and one int64 timestamp per group and window).  Padding, gathers'
index vectors, partial-aggregate blocks and re-reads are what the
program adds on top: they are its overhead, not the algorithm's need.
bench.py's guard of >= 17 B/point was the same count plus a mask byte;
the mask is the program's representation, so it is not counted here."""

BYTES_PER_POINT = 16


def request_bytes(req: dict, groups: int) -> int:
    """`req` as traffic.Generator makes it; `groups` as the reference
    answered (one row of windows each)."""
    if req.get("kind") == "last":
        return req["points"] * BYTES_PER_POINT
    span = req["end"] - req["start"] + 1
    windows = -(-span // req["interval_s"])
    return req["points"] * BYTES_PER_POINT + groups * windows * 16


def roofline_seconds(nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: these kernels do a handful of
    flops per 16 bytes, so HBM bandwidth is the bound, not FLOP/s."""
    return nbytes / peaks["hbm_bytes_per_s"]
