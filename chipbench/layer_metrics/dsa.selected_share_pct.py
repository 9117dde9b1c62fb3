"""How far the selection bites in the window: the rows the model selects
(``min(held, topk)``) over the rows the slots held, for the slot-ticks
served, from the program's own counters
(``summary()["kv_pool"]["sparse_positions_selected"]`` over
``["sparse_positions_valid"]``). 100 where every context fits the top-k
and the indexer decides nothing."""

from chipbench.layer_metrics import _latent_ops as L


def read(run):
    selected = L.section_delta(run, "kv_pool", "sparse_positions_selected")
    held = L.section_delta(run, "kv_pool", "sparse_positions_valid")
    if not selected or not held:
        return None
    return 100.0 * selected / held
