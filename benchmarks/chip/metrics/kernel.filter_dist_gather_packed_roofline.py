"""Share of the HBM byte roofline that the packed gather kernel
(``kernels/filter_dist.py`` ``filter_dist_gather_packed``) reaches: the
bytes its calls fetch, over its summed device time times the chip's HBM
bandwidth.

The kernel's calls are the device operations named after its Pallas
function, ``filter_dist_gather_packed_pallas.<n>`` (one per instantiation:
the graph loop's and the wide loop's). The bytes come from the shapes in
each call's HLO text, by :func:`gather_bytes`. They count what the call is handed: every one of the
``te`` rows of each tile, masked rows included, because the kernel fetches
them.

The least time is the larger of bytes over the HBM bandwidth and FLOPs
over the MXU's float32 rate (the bf16 peak over the six passes a
``Precision.HIGHEST`` product takes). Per candidate row the kernel does
``2·W`` FLOPs against ``4·W`` bytes, so the FLOP time is about 1/80 of the
byte time: the roofline is the byte roofline.
"""
import re

SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
WORD = {"f32": 4, "s32": 4, "u32": 4}


def shapes(text):
    """(dtype, dims) of the output and then each operand, in order."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in SHAPE.findall(text)]


def gather_bytes(out, table, labels, qplanes):
    """Bytes one call of the packed gather kernel fetches and writes.

    ``out = (R, 1, te)``: one tile of ``te`` candidates per grid step;
    ``table = (n, 1, W)`` 32-bit words per row; ``labels = (n, 1, Wl)``;
    ``qplanes = (B, P, W)``, fetched once per query (its block index is
    the tile's query, which the grid visits in order)."""
    R, _, te = out
    W, Wl = table[2], labels[2]
    B, P = qplanes[0], qplanes[1]
    rows = R * te * W * 4            # every candidate row, masked ones too
    label_rows = R * Wl * 4          # one packed label row per tile
    per_cand = R * te * 4 * 4        # ids, norms, visited words, scales
    row_ids = 2 * R * te * 4         # this tile's and the next tile's row ids
    result = R * te * 4
    queries = B * P * W * 4
    return rows + label_rows + per_cand + row_ids + result + queries


def gather_flops(out, table):
    R, _, te = out
    return 2 * R * te * table[2]


KERNEL = "filter_dist_gather_packed_pallas"


def parse(text):
    """``(out, table, labels, qplanes)`` shapes of one call, from its HLO
    text: ``out = custom-call(states, cur, table, labels, ids, next ids,
    qplanes, ids, norms, words, scales)``."""
    head, _, rest = text.partition("custom-call(")
    operands = shapes(rest.split("custom_call_target", 1)[0])
    out = shapes(head)
    if len(out) != 1 or len(operands) != 11:
        raise ValueError(f"unexpected {KERNEL} call: {text[:300]}")
    return out[0][1], operands[2][1], operands[3][1], operands[6][1]


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    from benchmarks.chip import tracing

    bw = run.peaks["hbm_bytes_per_s"]
    flops = run.peaks["bf16_flops_per_s"] / 6.0
    nbytes = fl = ns = 0.0
    for op in tracing.kernel_calls(tr, KERNEL):
        out, table, labels, qp = parse(op.text)
        nbytes += gather_bytes(out, table, labels, qp)
        fl += gather_flops(out, table)
        ns += op.end - op.start
    if ns == 0:
        return None
    return 100.0 * max(nbytes / bw, fl / flops) / (ns * 1e-9)
