"""The latent read's share of the memory roofline where the pool holds a
row an attention BLOCK (two a layer): the cached rows the served tokens'
ticks need at their valid lengths (1,152 bytes a row a block, read once)
with their queries and outputs, over the read's time in the trace and the
chip's peak bytes a second. The read's operations are those of the tick
that read the pool or its scores, told by operand shape
(``chipbench/kernels/mla_blocks.py``); the scatter that writes the pool
(its result is the pool) is not the read. The
read also runs for slots that are idle or past their budget, and XLA's
fetches every slot's whole slab; those bytes are not needed and not
counted."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    tr = run.get("trace")
    if not tr or "experts_held" not in run["conf"]["deployment"]:
        return None
    k = L.kernels(run, "mla_blocks")
    pool = k.pool_pattern(run["conf"])
    s = L.seconds(
        run, f"{pool}|{k.scores_pattern(run['conf'])}", result_not=pool
    )
    if not s:
        return None
    positions = slot_ticks = 0
    for r in run["requests"]:
        before = 0
        for t, n in r["syncs"]:
            # A request's first token is the admission's, not a tick's.
            first, ticks = (1, n - 1) if before == 0 else (before, n)
            if tr["host_t0"] < t <= tr["host_t1"]:
                positions += k.positions_of_block(
                    run["prompt_window"], first, ticks
                )
                slot_ticks += ticks
            before += n
    need = k.read_bytes(run["conf"], positions, slot_ticks)
    return 100.0 * need / (s * run["peaks"]["hbm_bytes_s"])
