"""The single KV-backend resolver: one decision object for the serving pool.

The serving cache has four orthogonal axes — dense slot pool vs paged
block tables (``kv_pages``), compute-dtype vs int8 payloads
(``kv_dtype``), XLA gathered read vs the Pallas fill-bounded kernels
(``kv_kernel``), and single-device vs mesh-sharded pools (``mesh``) —
and until PR 13 the four composed by EXCLUSION: ``kv_pages`` rejected
any mesh outright and ``kv_kernel`` hard-disabled whenever a mesh was
set, so the two flagship optimizations could never serve together and
sharded serving had zero paged/kernel rows anywhere (ROADMAP item 1,
VERDICT r5 weak #1).

``resolve_kv_backend`` replaces those blanket branches with a
CAPABILITY PROBE: it validates only what is genuinely unsupported
(raising a precise, regression-tested error per exclusion) and returns
a ``KVBackend`` describing the composed configuration — which pool
layout, which payload dtype, whether the Pallas read engages and, when
it does not, the machine-readable reason (surfaced on
``ServeMetrics`` so the ``kv_kernel="auto"`` threshold decision is
observable instead of silent).

Genuine exclusions (each raises):

- ``kv_pages`` + MoE: the paged suffix prefill routes experts densely
  (decode's rule) while the dense prefill uses the training dispatch —
  serving both would break the cache-on/off exactness contract.
- ``kv_kernel=True`` that cannot be honored (tiling shapes, block
  size, or a mesh the slots/heads don't divide): require-or-raise, so
  a benchmark never misattributes the XLA read's numbers to the
  kernel.

Everything else composes. Under a mesh the pools shard exactly like
the dense slot pool — kv heads over ``tp``, per-slot state over
``data`` — with the paged BLOCK pools replicated over ``data`` (blocks
are shared storage addressed by every slot's table; the per-slot
tables themselves are replicated operands) and the Pallas reads
wrapped in ``shard_map`` (``ops.kvattn.*_sharded`` — the
``flash_attention_sharded`` precedent: batch/head-parallel attention
needs no collectives, so each (data, tp) shard runs the kernel over
its own slots and heads).

What a layout of the dense server MEANS (its tensors and shardings, an
admission's rows, a tick's layer walk and step, its meters) is one class
of ``kvcache/slot_pool.py``, which ``KVBackend.layout`` selects.
"""

from __future__ import annotations

import dataclasses

__all__ = ["KVBackend", "resolve_kv_backend"]

# Pool length at/above which kv_kernel="auto" engages the Pallas reads:
# the kernels' advantage grows with pool bytes while their fixed
# in-tick cost does not. The threshold dates from a machine that is
# gone and has no benchmark cell on its short side (ROADMAP C4).
KV_KERNEL_AUTO_MIN_POOL = 1024


@dataclasses.dataclass(frozen=True)
class KVBackend:
    """The resolved serving-cache configuration — what actually serves.

    ``layout``: "dense" (per-slot pool), "paged" (block pool + per-
    slot tables), "latent" (a latent-attention config's per-slot pool:
    ONE tensor [L, B, M, rank + rope] in the compute dtype, read
    absorbed) or "by_kind" (a config with kinds of layer,
    ``window_pattern``: per-slot pools allocated by kind in the compute
    dtype, the full layers' K and V [Lf, B, M, K * Dh] and the window
    layers' rings [Lw, B, sliding_window, K * Dh], a position's kv heads
    side by side in one row) or "state" (a config with linear layers,
    ``linear_pattern``: slot memory by kind, a linear layer's recurrent
    state in float32 and its conv tail in the compute dtype, which no
    position indexes, by the recurrence's kind, ``linear_kind``: "kda"
    [L_lin, B, H, E, E] and [L_lin, B, taps - 1, 3 * H * E]; "ssd" [L_lin,
    B, H, P, N] and [L_lin, B, (taps - 1) * (H * P + 2 N)], a slot's rows
    in one; "conv" NO state and [L_lin, B, (taps - 1) * D] alone; beside the
    attention layers' pool by THEIR kind: the latent rows [L_att, B, M,
    rank + rope], or K and V rows [L_att, B, M, K * Dh] twice, as a pool
    by kind's full layers) or "indexed" (learned sparse attention,
    ``index_topk``: a position's K row beside its V row in ONE row of
    32-bit words, a tile of its own [L, B, M, W / 128, 128], two bfloat16
    a word, and its index key
    [L, B, Di, M], positions along the lanes: ``ops/dsa.py`` scores the
    keys and fetches the selected rows by index). ``int8``: quantized
    payloads + group-wise scales.
    ``kernel``: the Pallas fill-bounded read engages on decode ticks.
    ``kernel_disabled_reason``: why it does NOT engage (None when it
    does, or when int8 was never requested — there is no kernel
    without an int8 pool). ``data``/``tp``: mesh axis extents (1 =
    unsharded axis; both 1 = single device). ``row_write`` (derived):
    who writes a decode tick's new rows into the pool — "kernel" where
    the dense int8 pool's Pallas read does it itself
    (``ops.kvattn.int8_decode_attention_dynlen`` with ``rows=``),
    "scatter" for XLA's scatters on every other path. ``partner``
    (layout "state"): the attention layers' pool, "latent" or "kv" rows.
    ``resumable`` (derived): can a PARTIAL journal hint warm-resume."""

    layout: str
    int8: bool
    kernel: bool
    kernel_disabled_reason: str | None
    chunked: bool
    data: int
    tp: int
    partner: str | None = None

    @property
    def paged(self) -> bool:
        return self.layout == "paged"

    @property
    def sharded(self) -> bool:
        return self.data > 1 or self.tp > 1

    @property
    def row_write(self) -> str:
        # The flag slot_pool.Int8Pool's step branches on (``use_kernel``).
        wrote_in_read = self.layout == "dense" and self.int8 and self.kernel
        return "kernel" if wrote_in_read else "scatter"

    @property
    def resumable(self) -> bool:
        # int8 pools never (exactness, the one contract warm resume keeps,
        # was traded away); the latent pool, the pool by kind, the state
        # and the indexed pool have no spelling of the resume prefill yet. Compute-dtype
        # K/V: the paged path always (prompt + emitted tokens ride the
        # chunk queue), the dense path unless the mesh has a data axis (its
        # [1, S] resume prefill has no batch to shard). Else: cold replay.
        exact_kv = self.layout in ("dense", "paged") and not self.int8
        return exact_kv and (self.paged or self.data == 1)

    def describe(self) -> dict:
        """The ``ServeMetrics`` ``kv_backend`` info payload."""
        return {
            "layout": self.layout,
            "kv_dtype": "int8" if self.int8 else "compute",
            "kernel": self.kernel,
            "row_write": self.row_write,
            "kernel_disabled_reason": self.kernel_disabled_reason,
            "chunked": self.chunked,
            "data": self.data,
            "tp": self.tp,
        }


def _kernel_probe_dense(cfg, max_len: int, on_tpu: bool) -> str | None:
    """None = honorable; else the reason the dynamic-length kernel
    cannot run on this dense pool."""
    from torchkafka_tpu.ops.kvattn import dynlen_block, kernel_applicable

    if not kernel_applicable(cfg.head_dim, max_len):
        return (
            f"tiling: head_dim={cfg.head_dim} % 128 or "
            f"pool_len={max_len} % 8"
        )
    if dynlen_block(max_len) < (256 if on_tpu else 8):
        return (
            f"tiling: pool_len={max_len} has no >= 256 DMA block "
            f"(dynlen_block={dynlen_block(max_len)})"
        )
    return None


def _kernel_probe_paged(cfg, block_size: int, on_tpu: bool) -> str | None:
    """None = honorable; else why the block-table kernel cannot run."""
    from torchkafka_tpu.ops.kvattn import paged_kernel_applicable

    # Tiling gates COMPILED Mosaic only; off-TPU the kernel runs in
    # Pallas interpret mode (the tests' differential path), which
    # accepts any shape.
    if on_tpu and not (
        paged_kernel_applicable(cfg.head_dim, block_size)
        and block_size >= 256
    ):
        return (
            f"tiling: head_dim={cfg.head_dim} % 128, block_size="
            f"{block_size} % 128, and block_size >= 256 required on TPU"
        )
    return None


def _resolve_latent(cfg, *, mesh, kv_dtype, kv_kernel, kv_pages) -> KVBackend:
    """A latent-attention config's pool: what is built, and a reasoned
    refusal of every combination that is not."""
    what = "the latent (MLA) slot pool"
    if kv_dtype == "int8":
        raise ValueError(
            f"{what} is compute-dtype only: kv_dtype='int8' quantises "
            "(position, head) groups of K and V, and the latent row has "
            "neither heads nor a scale scheme yet"
        )
    if kv_pages is not None:
        raise ValueError(
            f"{what} is a dense per-slot pool: kv_pages (block tables, "
            "the radix prefix cache, its host tier and the prefill "
            "hand-off cut from them) address K/V blocks of kv heads, "
            "which a latent row does not have"
        )
    if mesh is not None and mesh.size > 1:
        raise ValueError(
            f"{what} serves on one device: the pool has no head axis to "
            "shard over tp, and the routed expert layer holds every "
            "expert (no exchange across chips is built)"
        )
    if kv_kernel is True:
        raise ValueError(
            f"{what} is read by XLA: no Pallas read is built for it, and "
            "kv_kernel=True never falls back silently"
        )
    return KVBackend(
        layout="latent", int8=False, kernel=False,
        kernel_disabled_reason=None, chunked=False, data=1, tp=1,
    )


def _resolve_state(cfg, *, mesh, kv_dtype, kv_kernel, kv_pages) -> KVBackend:
    """The slot memory of a config with linear layers (either
    recurrence, beside either attention's pool): what is built, and a
    reasoned refusal of every combination that is not."""
    kind = getattr(cfg, "linear_kind", "kda")
    what = (
        "the slot memory of linear layers (linear_pattern, linear_kind="
        f"{kind!r})"
    )
    # The gated convolution keeps a conv tail and no state: what the other
    # kinds cannot have, it has not been BUILT with.
    stateless = kind == "conv"
    if kv_dtype == "int8":
        raise ValueError(
            f"{what} keeps compute-dtype conv tails beside the attention "
            "layers' K and V rows, and the int8 rows (scales a position "
            "beside the tails, the quantising write of an admission and a "
            "tick in this layout) are not built for it"
            if stateless else
            f"{what} keeps a float32 recurrent state: kv_dtype='int8' "
            "quantises rows of K and V a position, and a state that every "
            "token rewrites has no scale scheme that holds over a thousand "
            "updates yet"
        )
    if kv_pages is not None:
        raise ValueError(
            f"{what} keeps a conv tail a slot beside rows a position: the "
            "tail after a prefix is a function of the prefix's last rows, "
            "so blocks of positions could carry it, but kv_pages (block "
            "tables, the radix prefix cache, its host tier and the prefill "
            "hand-off cut from them) are not built to keep a tail a block"
            if stateless else
            f"{what} is a state a slot, not rows a position: kv_pages "
            "(block tables, the radix prefix cache, its host tier and the "
            "prefill hand-off cut from them) share and rebuild a cache by "
            "BLOCKS OF POSITIONS, and the state after a prefix is no block "
            "of anything (a snapshot a prefix is not built)"
        )
    if mesh is not None and mesh.size > 1:
        raise ValueError(
            f"{what} serves on one device: no sharded layout has been "
            f"taught the {'tails' if stateless else 'state'}, and the "
            "routed expert layer's held share "
            "has no exchange across chips behind it"
        )
    if kv_kernel is True:
        own = (
            "is rolled by XLA beside the projection it follows "
            "(tk_gconv_step is a fusion, not a kernel)" if stateless else
            "is passed over by its own kernel (tk_kda_step, tk_ssd_step)"
        )
        raise ValueError(
            f"{what} {own} and the attention layers' compute-dtype pool "
            "(latent rows, or K and V rows) is read by XLA: kv_kernel=True "
            "asks for the int8 pool's Pallas read, and never falls back "
            "silently"
        )
    return KVBackend(
        layout="state", int8=False, kernel=False,
        kernel_disabled_reason=None, chunked=False, data=1, tp=1,
        partner="latent" if getattr(cfg, "is_mla", False) else "kv",
    )


def _resolve_by_kind(cfg, *, mesh, kv_dtype, kv_kernel, kv_pages) -> KVBackend:
    """The pool of a config with kinds of layer: what is built, and a
    reasoned refusal of every combination that is not."""
    what = "the slot pool by layer kind (window_pattern)"
    if kv_dtype == "int8":
        raise ValueError(
            f"{what} is compute-dtype only: kv_dtype='int8' comes with "
            "the dyn-len read, which bounds a slot's rows from above "
            "(its fill) and has no lower bound or ring order to read a "
            "window layer by"
        )
    if kv_pages is not None:
        raise ValueError(
            f"{what} is two dense per-slot pools: kv_pages (block tables, "
            "the radix prefix cache, its host tier and the prefill "
            "hand-off cut from them) address ONE pool in which every "
            "layer holds every position, and a window layer holds a ring"
        )
    if mesh is not None and mesh.size > 1:
        raise ValueError(
            f"{what} serves on one device: no sharded layout has been "
            "taught the two pools, and the routed expert layer holds "
            "every expert (no exchange across chips is built)"
        )
    if kv_kernel is True:
        raise ValueError(
            f"{what} is read by XLA: the Pallas reads take an int8 pool "
            "of whole contexts, and kv_kernel=True never falls back "
            "silently"
        )
    return KVBackend(
        layout="by_kind", int8=False, kernel=False,
        kernel_disabled_reason=None, chunked=False, data=1, tp=1,
    )


def _resolve_indexed(cfg, *, mesh, kv_dtype, kv_kernel, kv_pages) -> KVBackend:
    """The pool of learned sparse attention: what is built, and a reasoned
    refusal of every combination that is not."""
    what = "the indexed slot pool (learned sparse attention, index_topk)"
    if kv_dtype == "int8":
        raise ValueError(
            f"{what} is compute-dtype only: kv_dtype='int8' quantises "
            "(position, head) groups of K and V for the dyn-len read, and "
            "neither the selected read (a row a DMA, whole words) nor the "
            "index keys have a scale scheme"
        )
    if kv_pages is not None:
        raise ValueError(
            f"{what} is a dense per-slot pool: kv_pages (block tables, the "
            "radix prefix cache, its host tier and the prefill hand-off cut "
            "from them) address blocks of K and V rows, and a position "
            "here holds an index key besides, which no block carries"
        )
    if mesh is not None and mesh.size > 1:
        raise ValueError(
            f"{what} serves on one device: no sharded layout has been "
            "taught the index keys, and the routed expert layer's held "
            "share has no exchange across chips behind it"
        )
    if kv_kernel is True:
        raise ValueError(
            f"{what} is read by its own kernels (tk_dsa_index, "
            "tk_dsa_attend): kv_kernel=True asks for the int8 pool's Pallas "
            "read, and never falls back silently"
        )
    return KVBackend(
        layout="indexed", int8=False, kernel=False,
        kernel_disabled_reason=None, chunked=False, data=1, tp=1,
    )


def _mesh_kernel_reason(cfg, mesh, slots: int) -> str | None:
    """None = the shard_map wrapping works on this mesh; else why not.

    The sharded kernels run per (data, tp) shard over local slots and
    local kv heads, so both must split evenly (``check_serving_mesh``
    enforces the same divisibilities for the XLA path — this re-states
    them as a kernel capability so ``auto`` degrades with a reason
    instead of a deep shape error)."""
    data = mesh.shape.get("data", 1)
    tp = mesh.shape.get("tp", 1)
    if data > 1 and slots % data:
        return f"mesh: slots={slots} % data={data}"
    if tp > 1 and (cfg.n_kv_heads % tp or cfg.n_heads % tp):
        return (
            f"mesh: n_kv_heads={cfg.n_kv_heads}/n_heads={cfg.n_heads} "
            f"% tp={tp}"
        )
    return None


def resolve_kv_backend(
    cfg,
    *,
    mesh=None,
    kv_dtype: str | None = None,
    kv_kernel: bool | str = "auto",
    kv_pages=None,
    max_len: int,
    slots: int,
    backend: str | None = None,
) -> KVBackend:
    """Validate one KV-backend combination and decide kernel engagement.

    Raises ``ValueError`` for the genuine exclusions (module
    docstring); otherwise returns the composed ``KVBackend``.
    ``backend``: the jax platform string ("tpu"/"cpu"/...) — off-TPU
    the kernels run in interpret mode, so ``auto`` never engages them
    there while ``True`` still honors the request for the tests'
    differential path."""
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    # Identity checks, not ``in (True, False, 'auto')``: bool-int
    # equality would accept 1/0 and then treat them inconsistently
    # downstream (``kv_kernel is True`` guards would not fire for 1).
    if not (kv_kernel is True or kv_kernel is False or kv_kernel == "auto"):
        raise ValueError(
            f"kv_kernel must be True, False or 'auto', got {kv_kernel!r}"
        )
    if getattr(cfg, "linear_pattern", ()):
        return _resolve_state(
            cfg, mesh=mesh, kv_dtype=kv_dtype, kv_kernel=kv_kernel,
            kv_pages=kv_pages,
        )
    if getattr(cfg, "is_mla", False):
        return _resolve_latent(
            cfg, mesh=mesh, kv_dtype=kv_dtype, kv_kernel=kv_kernel,
            kv_pages=kv_pages,
        )
    if getattr(cfg, "is_sparse", False):
        return _resolve_indexed(
            cfg, mesh=mesh, kv_dtype=kv_dtype, kv_kernel=kv_kernel,
            kv_pages=kv_pages,
        )
    if getattr(cfg, "window_pattern", ()):
        return _resolve_by_kind(
            cfg, mesh=mesh, kv_dtype=kv_dtype, kv_kernel=kv_kernel,
            kv_pages=kv_pages,
        )
    int8 = kv_dtype == "int8"
    if kv_kernel is True and not int8:
        raise ValueError("kv_kernel requires kv_dtype='int8'")
    paged = kv_pages is not None
    if paged:
        if cfg.is_moe:
            raise ValueError(
                "kv_pages does not serve MoE configs: the paged suffix "
                "prefill routes experts densely (decode's rule) while "
                "the dense prefill uses the training dispatch, which "
                "would break the cache-on/off exactness contract"
            )
    on_tpu = backend == "tpu"
    data = mesh.shape.get("data", 1) if mesh is not None else 1
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1

    kernel = False
    reason: str | None = None
    if int8 and kv_kernel:
        if paged:
            reason = _kernel_probe_paged(cfg, kv_pages.block_size, on_tpu)
        else:
            reason = _kernel_probe_dense(cfg, max_len, on_tpu)
        if reason is None and mesh is not None:
            reason = _mesh_kernel_reason(cfg, mesh, slots)
        if kv_kernel is True:
            if reason is not None:
                raise ValueError(
                    f"kv_kernel=True cannot be honored here ({reason}); "
                    "the explicit request never falls back silently — a "
                    "benchmark must not misattribute the XLA read's "
                    "numbers to the kernel"
                )
            kernel = True
        else:  # "auto": engage only on TPU at or above the threshold
            if reason is None:
                if not on_tpu:
                    reason = f"auto: backend={backend!r} is not tpu"
                elif max_len < KV_KERNEL_AUTO_MIN_POOL:
                    reason = (
                        f"auto: pool_len={max_len} < "
                        f"{KV_KERNEL_AUTO_MIN_POOL}"
                    )
                else:
                    kernel = True
    elif kv_kernel and not int8:
        # auto without int8: there is no kernel for compute-dtype pools.
        reason = "auto: kv_dtype is not 'int8'"
    return KVBackend(
        layout="paged" if paged else "dense",
        int8=int8,
        kernel=kernel,
        kernel_disabled_reason=None if kernel else reason,
        chunked=paged,
        data=data,
        tp=tp,
    )
