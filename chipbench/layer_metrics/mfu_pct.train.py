"""Model FLOPs utilisation over the whole window: FLOPs a token (6 a
matmul parameter plus causal attention, recomputation not counted) times
tokens a second, over chips times the peak in bfloat16."""

from chipbench import stats


def read(run):
    toks = sum(s["tokens"] for s in run["steps"] if s["committed"])
    rate = toks / run["window_s"]
    return stats.mfu_pct(
        rate, stats.train_flops_per_token(run["dims"], run["seq"]),
        run["chips"], run["peaks"]["bf16_flops"],
    )
