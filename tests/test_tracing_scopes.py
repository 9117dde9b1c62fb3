"""The device programs name their parts (PR 38): every scope of
``utils/tracing.py`` is documented where the spans are, and each of the
five program families, compiled at toy size on the CPU, carries the
scopes it should and leaves few of its leaf instructions under none, so a
refactor that drops a scope fails here, without a chip.

What is counted is what ``chipbench/layer_metrics/_scopes.py`` joins with
a trace: the compiled module's instructions outside fused computations
(a fusion counts once, by its own op_name or by what most of its fused
instructions name), containers and the instructions that move no data
left out. The CPU's compiler fuses otherwise than the TPU's, so the
stated counts are this backend's; what a chip's trace leaves unscoped by
TIME is PERF.md's to say.
"""

from __future__ import annotations

import re
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import torchkafka_tpu as tk  # noqa: E402
from chipbench.layer_metrics import _scopes  # noqa: E402
from torchkafka_tpu.models import make_train_step  # noqa: E402
from torchkafka_tpu.models.transformer import (  # noqa: E402
    RopeKind, TransformerConfig, init_params,
)
from torchkafka_tpu.serve import StreamingGenerator  # noqa: E402
from torchkafka_tpu.utils import tracing  # noqa: E402

SCOPES = {k: v for k, v in vars(tracing).items() if k.startswith("SCOPE_")}
P, NEW, VOCAB = 16, 8, 64
NOT_WORK = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "while", "call", "conditional", "after-all", "partition-id", "iota",
}


@pytest.mark.parametrize("const", sorted(SCOPES))
def test_every_scope_is_listed_where_the_spans_are(const):
    name = SCOPES[const]
    assert re.fullmatch(r"tk_[a-z_]+", name) and ":" not in name
    assert name in _scopes.SCOPES
    # In the module's docstring with the functions that open it, and in
    # PERF.md's table of layers beside the metric that reads it.
    assert re.search(rf"^    {name}\s+\S", tracing.__doc__, re.M), name
    layers = (REPO / "PERF.md").read_text().split("## 3. Layers")[1].split("## 4.")[0]
    assert f"`{name}`" in layers, f"PERF.md §3 does not name {name}"
    # Opened somewhere in the program, by its constant.
    used = sum(
        f"tracing.{const}" in p.read_text() or f"xprof.{const}" in p.read_text()
        for p in (REPO / "torchkafka_tpu").rglob("*.py")
    )
    assert used, f"{const} is opened nowhere"


def test_a_scope_is_a_named_scope_and_costs_nothing_compiled():
    def f(x):
        with tracing.scope(tracing.SCOPE_FFN):
            return jnp.tanh(x) * 2.0

    def g(x):
        return jnp.tanh(x) * 2.0

    x = jnp.ones((8, 8))
    named, bare = (jax.jit(h).lower(x).compile().as_text() for h in (f, g))
    assert "tk_ffn" in named and "tk_ffn" not in bare
    def strip(text):  # the metadata, the tables of source locations, the names
        text = re.sub(
            r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*?\n",
            "", text, flags=re.M,
        )
        return re.sub(r"metadata=\{[^}]*\}|jit_[fg]|\bf\b|\bg\b", "", text)

    assert strip(named) == strip(bare)


def test_a_cached_executable_keeps_the_names_it_was_compiled_with(tmp_path):
    """Importing ``utils/tracing.py`` puts metadata into the persistent
    compilation cache's key: two programs that differ by a scope's name
    alone get an entry each, so neither is handed the other's HLO (on
    the chip a tick read from its parent's entry showed no scope)."""
    from jax._src import compilation_cache

    assert jax.config.jax_compilation_cache_include_metadata_in_key
    root = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.sub(root, "", tracing.__file__) == "torchkafka_tpu/utils/tracing.py"

    def program(name):
        def f(x):
            with tracing.scope(name):
                return jnp.tanh(x) * 2.0

        return jax.jit(f)

    knobs = {
        "jax_compilation_cache_dir": str(tmp_path),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    before = {k: getattr(jax.config, k) for k in knobs}
    try:
        for k, v in knobs.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        x = jnp.ones((8, 8))
        for name in (tracing.SCOPE_FFN, tracing.SCOPE_HEAD, tracing.SCOPE_FFN):
            program(name)(x).block_until_ready()
        assert len(list(tmp_path.glob("jit_f-*"))) == 2
    finally:  # the suite itself runs without a persistent cache
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ------------------------------------------------ the five families


def leaf_scopes(compiled) -> Counter:
    """Scope -> the count of the compiled module's leaf instructions that
    ``_scopes.module_scopes`` puts under it."""
    exe = compiled.runtime_executable()
    proto = exe.hlo_modules()[0].as_serialized_hlo_module_proto()
    _name, scopes = _scopes.module_scopes(memoryview(proto))
    # The computations that run as programs: the entry, and what whiles,
    # calls and conditionals reach from it (not fused computations, not
    # the reducers of a reduce or a sort).
    bodies, at = {}, None
    for line in compiled.as_text().split("\n"):
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            at = head.group(2)
            bodies[at] = {"entry": bool(head.group(1)), "rows": []}
            continue
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*?[\]\})] ([a-z\-]+)\(", line)
        if m and at:
            bodies[at]["rows"].append((m.group(1), m.group(2), line))
    todo = [n for n, b in bodies.items() if b["entry"]]
    reached = set()
    while todo:
        n = todo.pop()
        if n in reached or n not in bodies:
            continue
        reached.add(n)
        for _i, opcode, line in bodies[n]["rows"]:
            if opcode in ("while", "call", "conditional"):
                todo += re.findall(
                    r"(?:condition|body|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)", line,
                )
                for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                    todo += [c.strip().lstrip("%") for c in group.split(",")]
    out = Counter()
    for n in reached:
        for name, opcode, _line in bodies[n]["rows"]:
            if opcode not in NOT_WORK:
                out[scopes.get(name, "missing")] += 1
    return out


def serving_programs(cfg, **kw):
    params = init_params(jax.random.key(3), cfg)
    broker = tk.InMemoryBroker()
    broker.create_topic("p", partitions=1)
    consumer = tk.MemoryConsumer(broker, "p", group_id="g")
    slots = 4
    server = StreamingGenerator(
        consumer, params, cfg, slots=slots, prompt_len=P, max_new=NEW,
        ticks_per_sync=2, **kw,
    )

    def jitted(fn):
        return next(
            c.cell_contents for c in fn.__closure__
            if hasattr(c.cell_contents, "lower")
        )

    state = (server._caches, server._last_tok, server._pos, server._gen)
    mask = jnp.ones((slots,), bool)
    keys = server._slot_keys
    tick = jitted(server._tick_fn).lower(params, *state, mask, keys).compile()
    admit = jitted(server._admit_fn).lower(
        params, *state, jnp.zeros((slots, P), jnp.int32), mask, keys
    ).compile()
    return {"tick": tick, "admit": admit}


LATENT = dict(
    vocab_size=VOCAB, d_model=32, n_heads=2, n_kv_heads=2, d_ff=48,
    max_seq_len=P + NEW, dtype=jnp.float32, kv_lora_rank=16, qk_nope_dim=8,
    qk_rope_dim=4, v_head_dim=8, rope_interleave=True, n_experts=8,
    expert_d_ff=12,
)


def dense_int8():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=48, max_seq_len=P + NEW, dtype=jnp.float32,
    )
    return serving_programs(cfg, kv_dtype="int8", kv_kernel=False)


def latent_experts():
    return serving_programs(TransformerConfig(
        **LATENT, n_layers=3, first_dense_layers=1, expert_top_k=2,
        n_shared_experts=2, router_score="sigmoid", routed_scaling=2.448,
    ))


def double_layer_share():
    return serving_programs(TransformerConfig(
        **LATENT, n_layers=2, q_lora_rank=12, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, attn_blocks=2, zero_experts=4, expert_top_k=3,
        router_score="softmax", norm_topk=False, routed_scaling=6.0,
        experts_held=(2, 4),
    ))


def window_full_experts():
    return serving_programs(TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=48, max_seq_len=P + NEW, dtype=jnp.float32, stated_head_dim=16,
        sliding_window=8, window_pattern=(True, True, True, False),
        rope_theta=500000.0, rope_full=RopeKind(
            theta=500000.0, factor=16.0, original_len=64, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782,
        ),
        n_experts=8, expert_top_k=2, expert_d_ff=24, router_score="softmax",
        norm_topk=True,
    ))


def training_step():
    import optax

    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=48, max_seq_len=32, dtype=jnp.float32, remat=True,
        ce_block_size=16,
    )
    mesh = tk.make_mesh({"data": 1}, devices=jax.devices()[:1])
    opt = optax.adamw(3e-4)
    init_fn, step_fn = make_train_step(cfg, mesh, opt)
    params, opt_state = init_fn(jax.random.key(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    return {"step": step_fn.lower(params, opt_state, tokens, tokens).compile()}


DENSE = {"tk_embed", "tk_attn_proj", "tk_kv_write", "tk_ffn", "tk_head"}
MOE = {"tk_moe_route", "tk_moe_dispatch", "tk_moe_experts"}
# family -> (builder, {program: (the scopes it must carry, the most leaf
# instructions it may leave under none)}). The counts are what this tree
# reads on the CPU and a few more.
FAMILIES = {
    "dense GQA, int8 KV": (dense_int8, {
        "tick": (DENSE | {"tk_kv_read"}, 32),
        "admit": (DENSE | {"tk_attn_flash"}, 38),
    }),
    "MLA with experts": (latent_experts, {
        "tick": (DENSE | MOE | {"tk_kv_read_latent"}, 38),
        # 4 rows of 16 tokens, top-2 over 8 experts, are 16 pairs an expert:
        # the grouped form since PR 39, as the cells' admissions take it. Its
        # kernels the interpreter unrolls here into loops with no name (on
        # the chip each is one call under ``tk_moe_experts``); so below.
        "admit": (DENSE | MOE | {"tk_attn_flash"}, 62),
    }),
    "the held share's double layer": (double_layer_share, {
        "tick": (DENSE | MOE | {"tk_kv_read_latent"}, 56),
        "admit": (DENSE | MOE | {"tk_attn_flash"}, 58),
    }),
    "window/full with experts": (window_full_experts, {
        "tick": ((DENSE | MOE | {"tk_kv_read_window", "tk_kv_read_full"})
                 - {"tk_ffn"}, 92),
        "admit": ((DENSE | MOE | {"tk_attn_flash"}) - {"tk_ffn"}, 142),
    }),
    "the training step": (training_step, {
        "step": ({"tk_embed", "tk_attn_proj", "tk_attn_flash", "tk_ffn",
                  "tk_head", "tk_loss", "tk_optimizer"}, 53),
    }),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_s_programs_carry_their_scopes(family):
    build, expected = FAMILIES[family]
    programs = build()
    assert set(programs) == set(expected)
    for name, (must, most_unscoped) in expected.items():
        split = leaf_scopes(programs[name])
        print(family, name, dict(split))
        assert "missing" not in split
        named = set(split) - {_scopes.UNSCOPED}
        assert named == must, (family, name, sorted(named ^ must))
        assert split[_scopes.UNSCOPED] <= most_unscoped, (family, name, split)
        # Most of the work has a name.
        assert split[_scopes.UNSCOPED] < 0.5 * sum(split.values())
