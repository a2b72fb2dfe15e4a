"""Compile the decode-path Pallas kernels for a TPU v5e that is described,
not attached: the TPU compiler refuses block shapes, layouts and VMEM use
that interpret mode (every other kernel test) never checks.

Shapes are the published widths of the served configurations (phi3-mini,
qwen3-8b, mamba2-130m).  Nothing runs; each test asserts the compiled
program holds the Mosaic kernel (``tpu_custom_call``), i.e. no jnp or
interpreted fallback was compiled in its place.

The served programs keep the KV cache resident: at phi3-mini widths with
the kernels on, no program that writes the cache (the edge segment, the
cloud segment at buckets 4 and 8, an admission prefill, ``reset_rows``,
the overflow re-run's restore) copies it — neither a whole stacked leaf
nor a per-layer slice, in any layout — and every cache input is donated
into an output (``input_output_alias``), so each updates it in place.
At phi3-mini's full 32 layers the edge segment and an admission read the
executor's served weights without copying any of them.

Served tensor-parallel on a described four-chip host at qwen3-8b's
published widths, the same programs keep the sharded cache in place too,
move no part of it between chips, all-reduce twice a layer, and fit a
chip's memory with the longest admission.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.entropy_exit import (
    entropy_exit_argmax_heads_pallas,
    entropy_exit_argmax_pallas,
    entropy_exit_pallas,
)
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas, ssd_update_pallas
from repro.models import model as M
from repro.serving.partitioned import PartitionedServer

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed / usable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    """fn, *(shape, dtype) -> compiled HLO text for the described chip."""

    def go(fn, *args):
        sds = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
        return jax.jit(fn).lower(*sds).compile().as_text()

    return go


@pytest.mark.parametrize(
    "b,h,kh,d,c",
    [
        (8, 32, 32, 96, 4096),  # phi3-mini: MHA, head_dim 96
        (8, 32, 8, 128, 4096),  # qwen3-8b: GQA 4:1
    ],
)
def test_flash_decode_compiles(compile_tpu, b, h, kh, d, c):
    text = compile_tpu(
        lambda q, k, v, kp, qp, rows: flash_decode_pallas(
            q, k, v, kp, qp, rows, interpret=False
        ),
        ((b, h, d), BF16), ((b, c, kh, d), BF16), ((b, c, kh, d), BF16),
        ((b, c), I32), ((b,), I32), ((b,), I32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [8, 32])
def test_entropy_exit_argmax_compiles(compile_tpu, b):
    text = compile_tpu(
        lambda l: entropy_exit_argmax_pallas(l, 0.5, interpret=False),
        ((b, 32064), BF16),
    )
    assert "tpu_custom_call" in text


def test_entropy_exit_compiles(compile_tpu):
    text = compile_tpu(
        lambda l: entropy_exit_pallas(l, 0.5, interpret=False),
        ((32, 32064), BF16),
    )
    assert "tpu_custom_call" in text


def test_entropy_exit_argmax_heads_compiles(compile_tpu):
    text = compile_tpu(
        lambda l, t: entropy_exit_argmax_heads_pallas(l, t, interpret=False),
        ((3, 8, 32064), BF16), ((3,), F32),
    )
    assert "tpu_custom_call" in text


def test_ssd_update_compiles(compile_tpu):
    # mamba2-130m decode: B8, H24, P64, N128, one B/C group.
    text = compile_tpu(
        lambda hs, x, a, bv, cv, rows: ssd_update_pallas(
            hs, x, a, bv, cv, rows, interpret=False
        ),
        ((8, 24, 64, 128), F32), ((8, 24, 64), F32), ((8, 24), F32),
        ((8, 1, 128), F32), ((8, 1, 128), F32), ((8,), I32),
    )
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles(compile_tpu):
    # mamba2-130m heads over a 2048-token prefill, 256-token chunks.
    text = compile_tpu(
        lambda x, a, bm, cm: ssd_scan_pallas(
            x, a, bm, cm, chunk=256, interpret=False
        ),
        ((2, 2048, 24, 64), BF16), ((2, 2048, 24), F32),
        ((2, 2048, 24, 128), BF16), ((2, 2048, 24, 128), BF16),
    )
    assert "tpu_custom_call" in text


def test_phi3_tier_segment_compiles(one_chip, no_persistent_cache,
                                    monkeypatch):
    """A 2-layer phi3-mini decode segment at published widths (bf16
    params, one branch head) with kernels on auto: steered to "on TPU",
    the segment must compile with flash_decode and the fused exit
    decision as Mosaic kernels."""
    from repro.models import model as M
    from repro.serving.tiers import TierExecutor, segments_for_cuts

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(
        get_config("phi3_mini_3_8b"), num_layers=2, branch_layers=(1,),
        param_dtype="bfloat16",
    )
    batch, context = 8, 1024

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            tree,
        )

    params = placed(jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0)
    ))
    caches = placed(jax.eval_shape(
        lambda: M.init_caches(cfg, batch, context)
    ))
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
    assert ex.use_kernels
    tok, pos, exited = placed((
        jax.ShapeDtypeStruct((batch, 1), I32),
        jax.ShapeDtypeStruct((batch,), I32),
        jax.ShapeDtypeStruct((batch,), jnp.bool_),
    ))
    compiled = ex.segment_fn(0).lower(
        params, tok, pos, exited, pos, caches
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


SLOTS, CONTEXT, LAYERS = 8, 512, 4


def cache_copies(text: str) -> list[str]:
    """Result shapes of the ``copy`` instructions that move a whole cache
    leaf, K/V ``[L, B, C, K, D]`` or slot positions ``[L, B, C]``, or one
    layer of one."""
    leaf = re.compile(rf"^(\d+,)?{SLOTS},{CONTEXT}(,\d+,\d+)?$")
    shapes = re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", text)
    return [s for s in shapes if leaf.match(s)]


def cache_params(text: str, is_cache=None) -> tuple[list[str], list[str]]:
    """Entry parameters holding a cache leaf, and those of them that no
    output aliases.  A cache leaf is a parameter named ``caches...``, or
    one that ``is_cache(name, shape)`` picks (a partitioned program's
    parameters lose their names)."""
    header = text.split("\n", 1)[0]
    aliased = {
        int(p) for p in
        re.findall(r"\(\s*(\d+),\s*\{[^}]*\},\s*[\w-]+-alias\)", header)
    }
    entry = re.search(r"^ENTRY [^(]*\((.*)\) -> ", text, re.M).group(1)
    params = re.findall(r"([\w.]+): [a-z]\w*\[([\d,]*)\]", entry)
    is_cache = is_cache or (lambda name, shape: name.startswith("caches"))
    cache = [(i, n) for i, (n, shape) in enumerate(params)
             if is_cache(n, shape)]
    return [n for _, n in cache], [n for i, n in cache if i not in aliased]


@pytest.fixture(scope="module")
def programs(one_chip, no_persistent_cache):
    """Compiled HLO text of each program that writes the cache."""
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "on_tpu", lambda: True)
    try:
        cfg = dataclasses.replace(
            get_config("phi3_mini_3_8b"), num_layers=LAYERS,
            branch_layers=(1,), param_dtype="bfloat16",
        )

        def placed(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip),
                tree,
            )

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        params = placed(jax.eval_shape(
            lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0)
        ))
        server = PartitionedServer(cfg, params, 2, slots=SLOTS,
                                   context_len=CONTEXT)
        ex = server.executor
        assert ex.use_kernels
        caches = placed(jax.eval_shape(
            lambda: ex.shard_caches(M.init_caches(cfg, SLOTS, CONTEXT))
        ))
        assert caches["blocks"]["self"]["k"].shape[-1] == 128  # 96, padded
        tok, pos = sds((SLOTS, 1), I32), sds((SLOTS,), I32)
        exited = sds((SLOTS,), jnp.bool_)
        hidden = sds((SLOTS, 1, cfg.d_model), BF16)
        edge = ex.segment_fn(0)
        out = {"edge": edge.lower(params, tok, pos, exited, pos, caches)}
        for b in (4, 8):
            out[f"cloud_b{b}"] = ex.segment_fn(1, b).lower(
                params, hidden, pos, exited, pos, caches
            )
        out["prefill"] = ex.prefill_fn(256, 1).lower(
            params, sds((1, 256), I32), sds((1,), I32), caches
        )
        out["reset"] = ex.reset_fn(1).lower(caches, sds((1,), I32))
        texts = {k: v.compile().as_text() for k, v in out.items()}
        snap = placed(jax.eval_shape(
            edge, params, tok, pos, exited, pos, caches
        )["snap"])
        texts["restore"] = ex._restorer(caches, snap).as_text()
        return texts
    finally:
        mp.undo()


@pytest.mark.parametrize(
    "name", ["edge", "cloud_b4", "cloud_b8", "prefill", "reset", "restore"]
)
def test_no_cache_copy_and_cache_donated(programs, name):
    text = programs[name]
    assert cache_copies(text) == []
    params, unaliased = cache_params(text)
    assert len(params) >= 3 and unaliased == []  # K, V, slots at least
    if name in ("edge", "cloud_b4", "cloud_b8"):
        assert "tpu_custom_call" in text  # flash_decode compiled in


#: phi3-mini at its full depth, cut where its cell cuts: at ``LAYERS`` the
#: scan is too short for XLA to hoist a copy of a whole weight stack.
FULL_LAYERS, FULL_CUT = 32, 16


def weight_copies(text: str, width: int) -> list[str]:
    """The ``copy`` instructions that read an entry parameter named
    ``params...`` or make a ``[..., width, width]`` result (a q/k/v weight,
    whole stack or one layer)."""
    square = re.compile(rf"^(\d+,)?{width},{width}$")
    return [
        m.group(0) for m in re.finditer(
            r"[\w.\-]+ = \w+\[([\d,]*)\]\S* copy\(%?([\w.\-]+)\)", text)
        if m.group(2).startswith("params") or square.match(m.group(1))
    ]


def test_phi3_full_depth_reads_weights_in_place(one_chip, no_persistent_cache,
                                                monkeypatch):
    """The 32-layer edge segment and a 256-token admission, compiled from
    the executor's served tree: with phi3's head width of 96 off the lanes
    the canonical q/k/v stacks were copied whole into the layout the dots
    read on every step (``bf16[32, 3072, 3072]``, three of them in the
    edge); served transposed, no program copies a weight."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(
        get_config("phi3_mini_3_8b"), num_layers=FULL_LAYERS,
        branch_layers=(8, 16, 24), param_dtype="bfloat16",
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def placed(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0)
    ))
    ex = PartitionedServer(cfg, params, FULL_CUT, slots=SLOTS,
                           context_len=CONTEXT).executor
    caches = placed(jax.eval_shape(
        lambda: ex.shard_caches(M.init_caches(cfg, SLOTS, CONTEXT))
    ))
    tok, pos = sds((SLOTS, 1), I32), sds((SLOTS,), I32)
    exited = sds((SLOTS,), jnp.bool_)
    edge = ex.segment_fn(0).lower(
        ex.params, tok, pos, exited, pos, caches
    ).compile().as_text()
    prefill = ex.prefill_fn(256, 1).lower(
        ex.params, sds((1, 256), I32), sds((1,), I32), caches
    ).compile().as_text()
    assert weight_copies(edge, cfg.d_model) == []
    assert weight_copies(prefill, cfg.d_model) == []
    assert "tpu_custom_call" in edge  # flash_decode compiled in


# ---------------------------------------------------------------- four chips
#: qwen3-8b served tensor-parallel on a (1, 4) mesh of the described v5e
#: host: 32 slots of 2048 positions, K=2 cut at layer 18, the longest
#: admission 32 prompts of 1536 tokens.
QWEN_SLOTS, QWEN_CONTEXT, QWEN_PROMPT, QWEN_CUT = 32, 2048, 1536, 18


def hlo_computations(text: str) -> dict[str, str]:
    """Each computation's body text by name (ENTRY under "ENTRY")."""
    out = {}
    for m in re.finditer(r"^(ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}",
                         text, re.M | re.S):
        out["ENTRY" if m.group(1) else m.group(2)] = m.group(3)
    return out


def loop_ops(text: str, opcode: str) -> int:
    """``opcode`` instructions executed by the while loops reachable from
    the entry computation: each loop body's count times its trip count
    (the s32 constant of its condition), nested loops included."""
    comps = hlo_computations(text)
    op = re.compile(rf" {re.escape(opcode)}(-start)?\(")

    def executed(name: str, in_loop: bool) -> int:
        body = comps[name]
        n = len(op.findall(body)) if in_loop else 0
        for cond, sub in re.findall(
                r" while\(.*condition=%([\w.\-]+), body=%([\w.\-]+)", body):
            trips = re.findall(r"s32\[\]\S* constant\((\d+)\)", comps[cond])
            assert len(trips) == 1, comps[cond][:400]
            n += int(trips[0]) * executed(sub, True)
        return n

    return executed("ENTRY", False)


def moved_shapes(text: str) -> list[list[int]]:
    """Result shapes of the all-gathers and all-to-alls."""
    shapes = re.findall(
        r"= \w+\[([\d,]*)\]\S* (?:all-gather|all-to-all)(?:-start)?\(", text)
    return [[int(d) for d in s.split(",") if d] for s in shapes]


@pytest.fixture(scope="module")
def mesh_programs(topo, no_persistent_cache):
    """Compiled programs of the qwen3-8b K=2 executor on the described
    (1, 4) mesh at published widths: ``(compiled, HLO text)`` by name."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.sharding.policy import ShardingPolicy, make_policy

    mp = pytest.MonkeyPatch()
    # A described device holds no arrays: the executor keeps the params as
    # the placed shapes it is handed.
    mp.setattr(ShardingPolicy, "shard_params", lambda self, params: params)
    try:
        mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        cfg = dataclasses.replace(get_config("qwen3_8b"),
                                  param_dtype="bfloat16")
        pol = make_policy(mesh, cfg)

        def placed(shapes, shardings):
            return jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                shapes, shardings)

        shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.random.PRNGKey(0))
        params = placed(shapes, pol.params_shardings(shapes))
        server = PartitionedServer(cfg, params, QWEN_CUT, slots=QWEN_SLOTS,
                                   context_len=QWEN_CONTEXT, mesh=mesh)
        ex = server.executor
        assert ex.sharded and not ex.use_kernels
        shapes = jax.eval_shape(
            lambda: M.init_caches(cfg, QWEN_SLOTS, QWEN_CONTEXT))
        caches = placed(shapes, pol.cache_shardings(shapes))
        rep = pol.replicated()

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        tok, pos = sds((QWEN_SLOTS, 1), I32), sds((QWEN_SLOTS,), I32)
        exited = sds((QWEN_SLOTS,), jnp.bool_)
        hidden = sds((QWEN_SLOTS, 1, cfg.d_model), BF16)
        lowered = {
            "edge": ex.segment_fn(0).lower(params, tok, pos, exited, pos,
                                           caches),
            "cloud_b32": ex.segment_fn(1, QWEN_SLOTS).lower(
                params, hidden, pos, exited, pos, caches),
            "prefill": ex.prefill_fn(QWEN_PROMPT, QWEN_SLOTS).lower(
                params, sds((QWEN_SLOTS, QWEN_PROMPT), I32),
                sds((QWEN_SLOTS,), I32), caches),
            "reset": ex.reset_fn(QWEN_SLOTS).lower(
                caches, sds((QWEN_SLOTS,), I32)),
            "new_caches": ex.cache_maker(QWEN_SLOTS, QWEN_CONTEXT).lower(),
        }
        out = {}
        for name, low in lowered.items():
            compiled = low.compile()
            out[name] = (compiled, compiled.as_text())
        return out
    finally:
        mp.undo()


MESH_WRITERS = ["edge", "cloud_b32", "prefill", "reset"]


@pytest.mark.parametrize("name", MESH_WRITERS)
def test_mesh_program_updates_the_cache_in_place(mesh_programs, name):
    """Every cache input is donated into an output, and no all-gather or
    all-to-all moves a cache leaf or a slice of one (a K/V or slot row
    has the context's 2048 positions, a chunk of it 1024)."""
    _, text = mesh_programs[name]
    # A per-chip shape with the slots and the context: K, V, slot positions.
    leaf = f"{QWEN_SLOTS},{QWEN_CONTEXT}"
    params, unaliased = cache_params(text, lambda _, shape: leaf in shape)
    assert len(params) == 3 and unaliased == [], params
    for shape in moved_shapes(text):
        assert QWEN_CONTEXT not in shape and QWEN_CONTEXT // 2 not in shape, \
            shape
        assert math.prod(shape) * 2 <= 1 << 20, shape


@pytest.mark.parametrize("name", ["edge", "cloud_b32"])
def test_mesh_decode_all_reduces_twice_a_layer(mesh_programs, name):
    """Tensor parallelism costs each layer two all-reduces: the attention
    output and the MLP's down projection (each tier holds 18 layers)."""
    _, text = mesh_programs[name]
    assert loop_ops(text, "all-reduce") == 2 * QWEN_CUT


@pytest.mark.parametrize("name", MESH_WRITERS + ["new_caches"])
def test_mesh_program_fits_a_chip(mesh_programs, name):
    """Arguments plus temporaries per chip stay under 15 GB of a v5e's 16
    (the admission prefill is the largest: 6.5 GB of weights and cache,
    about 6.2 GB of temporaries)."""
    compiled, _ = mesh_programs[name]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    if name == "new_caches":
        # Each chip creates its own quarter of the 9.7 GB cache.
        assert 2.3e9 < mem.output_size_in_bytes < 2.6e9
