import re

import counts
import families


def read(ctx, kernel, match):
    """Roofline share of one kernel over the traced training steps: the least
    time its calls could take (operations and bytes from shapes, the larger of
    the two bounds) over their device time. A Pallas kernel's trace event is
    its HLO line (`custom_call_target="tpu_custom_call"`, no kernel name), so
    `match` is a pattern over that line and the kernel's own operand shapes,
    worked out from the cell's sizes, tell the kernels of one step apart. Both
    the shapes and the count are the family's, found by the kernel's name:
    `<kernel>_operands` and `<kernel>_layer_step` of its counts.

    The layer-steps traced are the calls of EVERY instruction found, added up, over the calls a layer
    makes a step (the length of `layer_step`'s list): layers rolled into one loop run one instruction
    layers x steps times, layers unrolled run an instruction each, steps times, and both read alike.
    (Until PR 64 the least count of any one instruction stood for layers x steps, and an unrolled
    step read a quarter of its share: PERF.md section 6.)"""
    tr, run = ctx.get("trace"), ctx["run"]
    if not tr or ctx["device"].get("platform") != "tpu":
        return None
    w, own = run.w, families.counts(run.sizes)
    try:
        operands, layer_step = getattr(own, kernel + "_operands"), getattr(own, kernel + "_layer_step")
    except AttributeError:
        raise ValueError(f"no count for kernel {kernel!r}: {own.__file__} has no {kernel}_operands "
                         f"and {kernel}_layer_step") from None
    operands = re.compile(operands(run.sizes, w["seq_len"]))
    pat = re.compile(match)
    found = {n: t for n, t in tr["op_time_s"].items() if pat.search(n) and operands.search(n)}
    if not found:
        return None
    device_s = sum(found.values())
    peak = counts.peak_for(ctx["device"]["kind"], run.peaks)
    # rows of the batch one chip's attention sees: the batch is split over
    # the chips unless the layout replicates it (`batch_shards` in the file)
    rows = w["batch_size"] // w.get("batch_shards", run.chips)
    calls = layer_step(run.sizes, rows, w["seq_len"])
    layer_steps = sum(tr["op_count"][n] for n in found) / len(calls)
    per_layer_step = sum(counts.roofline_seconds(ops, nbytes, peak) for ops, nbytes in calls)
    return 100.0 * per_layer_step * layer_steps / device_s
