import counts
import families


def read(ctx):
    run = ctx["run"]
    if ctx.get("tok_s_chip") is None or ctx["device"].get("platform") != "tpu":
        return None  # a utilization of the chip comes only from the chip
    peak = counts.peak_for(ctx["device"]["kind"], run.peaks)
    flops = families.counts(run.sizes).train_flops_per_token(run.sizes, run.w["seq_len"])
    return 100.0 * flops * ctx["tok_s_chip"] / peak["bf16_flops_per_s"]
