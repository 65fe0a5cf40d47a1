import re

import counts


def read(ctx, kernel, match):
    """Roofline share of one kernel over the traced training steps: the least
    time its calls could take (operations and bytes from shapes, the larger of
    the two bounds) over their device time. A Pallas kernel's trace event is
    its HLO line (`custom_call_target="tpu_custom_call"`, no kernel name), so
    `match` is a pattern over that line and the kernel's own operand shapes,
    worked out from the cell's sizes, tell the kernels of one step apart."""
    tr, run = ctx.get("trace"), ctx["run"]
    if not tr or ctx["device"].get("platform") != "tpu":
        return None
    w = run.w
    if kernel != "flash":
        raise ValueError(f"no count for kernel {kernel!r}")
    # flash reads and writes [rows x heads, positions, head size] in the model's type
    own = re.compile(rf"\b\w+\[\d+,{w['seq_len']},{run.sizes['head_dim']}\]")
    pat = re.compile(match)
    found = {n: t for n, t in tr["op_time_s"].items() if pat.search(n) and own.search(n)}
    if not found:
        return None
    device_s = sum(found.values())
    # every kernel of the layer runs once for each layer and step traced
    calls = min(tr["op_count"][n] for n in found)
    peak = counts.peak_for(ctx["device"]["kind"], run.peaks)
    # rows of the batch one chip's attention sees: the batch is split over
    # the chips unless the layout replicates it (`batch_shards` in the file)
    rows = w["batch_size"] // w.get("batch_shards", run.chips)
    least = [counts.roofline_seconds(*counts.flash_call(run.sizes, rows, w["seq_len"], bwd), peak)
             for bwd in (False, True)]
    # full remat runs the forward once more inside the backward; that call's
    # time is in the device time, so its least time is counted too
    per_layer_step = 2 * least[0] + least[1]
    return 100.0 * per_layer_step * calls / device_s
