"""Share of its roofline that the Pallas edge-aggregate kernel (the
masked segment sum of per-edge messages into nodes) reaches in the
traced window, in %: the least time the chip could take for its calls
over their device time.

The least time is that of the segment sum's own work, counted here: per
event, one add per edge and channel (E·d, with E = ``in_edges``·n edge
slots and d = ``d_hidden``), and f32 bytes of the messages, the
destination ids, the edge mask and the (n, d) output. The kernel's
one-hot-incidence matmul does N·E·d multiply-adds instead; they are not
counted, so that a later kernel is read against the same work however
it sums. Each call covers one launch of ``b`` events (the P-search
gives this cell P equal to its microbatch). f32 operations are held
to the bf16 peak."""

from bench import flops, trace

PATTERN = r"^edge_aggregate(_batched)?(\.\d+)?$"


def cost(b, n, w):
    """Operations and bytes of one call on ``b`` events of ``n`` rows."""
    e, d = w["in_edges"] * n, w["d_hidden"]
    return {"f32": b * e * d,
            "bytes": b * 4 * (e * d + e + e + n * d)}


def read(rec):
    tr = rec["trace"]
    if not tr or rec["peaks"] is None:
        return None
    secs, calls = trace.kernel_time(tr["trace"], PATTERN, tr["t0"], tr["t1"])
    if calls == 0 or secs <= 0:
        return None
    least, _ = flops.min_seconds(
        cost(rec["launch"]["b"], rec["launch"]["n"], rec["widths"]),
        rec["peaks"])
    return 100.0 * least * calls / secs
