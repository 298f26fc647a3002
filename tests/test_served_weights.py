"""A server holds its weights in the compute dtype (`serving/server.py
load_params`, `models/common.py served_params`, each family's
`READ_AT_FLOAT32`): the cast that every decode and prefill program
repeated runs once at load. These tests hold the claim that nothing
served changes (bit for bit), that each family's table says what its
bodies do, and that the casts do not come back."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from polyaxon_tpu.models import lfm2, llama, moe, nemotron_h
from polyaxon_tpu.models.common import served_params
from polyaxon_tpu.serving.quantize import tree_bytes, weight_bytes
from polyaxon_tpu.serving.server import _Engine, load_params

FAMILIES = {"llama": ("llama_tiny", llama), "moe": ("moe_tiny", moe),
            "lfm2": ("lfm2_tiny", lfm2),
            "nemotron_h": ("nemotron_h_tiny", nemotron_h)}
PAGE = 4
N_PAGES = 8
PROMPT = [5, 6, 7, 1, 2, 3, 4, 9]          # two whole pages
SUFFIX = [8, 2, 11, 3]
PAGE_IDS = jnp.asarray([1, 2, 3, -1], jnp.int32)


def _row(fam) -> tuple:
    """What a prefill program is told beside the block table by a
    family whose cache has per-row leaves: the engine's row."""
    return (jnp.int32(0),) if hasattr(fam, "paged_init_rows") else ()


def _pool(fam, cfg) -> dict:
    cache = fam.paged_init_cache(cfg, N_PAGES, PAGE)
    if hasattr(fam, "paged_init_rows"):
        cache["rows"] = fam.paged_init_rows(cfg, 2)
    return cache


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


@functools.lru_cache(maxsize=None)
def _trees(family: str):
    """(cfg, the float32 tree, the served tree, the tree with every leaf
    cast). The float32-read leaves are drawn anew: a gain of exactly 1
    rounds to itself and would hide a leaf wrongly cast."""
    name, fam = FAMILIES[family]
    cfg = fam.CONFIGS[name]
    assert cfg.dtype == jnp.bfloat16
    drawn = jax.jit(lambda key: fam.init(cfg, key)["params"])(
        jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(7), 64))

    def redraw(path, leaf):
        if _leaf_name(path) not in fam.READ_AT_FLOAT32:
            return leaf
        return leaf + 0.1 * jax.random.normal(next(keys), leaf.shape)

    full = jax.tree_util.tree_map_with_path(redraw, drawn)
    served = served_params(full, cfg.dtype, fam.READ_AT_FLOAT32)
    every = jax.tree.map(lambda x: x.astype(cfg.dtype), full)
    return cfg, full, served, every


def _prefill(fam, cfg, params, family):
    prompt = jnp.asarray([PROMPT, PROMPT[::-1]], jnp.int32)
    return jax.jit(lambda p: fam.prefill(cfg, p, prompt, 32))(params)


@functools.lru_cache(maxsize=None)
def _pool_after_prefill(family: str):
    """The page pool holding PROMPT in pages 1 and 2, written from the
    float32 tree: what the decode step and the suffix prefill start
    from, whichever tree they then run on."""
    _, fam = FAMILIES[family]
    cfg, full, _, _ = _trees(family)
    prompt = jnp.asarray([PROMPT], jnp.int32)
    return jax.jit(lambda p, c: fam.paged_insert_prefill(
        c, *fam.paged_prefill_kv(cfg, p, prompt), PAGE_IDS, PAGE,
        *_row(fam)))(full, _pool(fam, cfg))


def _programs(family: str):
    """The engine's decode step, its whole-prompt prefill and its suffix
    prefill, as functions of (params, the pool after PROMPT's prefill)."""
    _, fam = FAMILIES[family]
    cfg = _trees(family)[0]
    tokens = jnp.asarray([SUFFIX[0], 0], jnp.int32)
    pos = jnp.asarray([len(PROMPT), -1], jnp.int32)       # row 1 idle
    tables = jnp.stack([PAGE_IDS, jnp.full_like(PAGE_IDS, -1)])
    prompt = jnp.asarray([PROMPT], jnp.int32)
    suffix = jnp.asarray([SUFFIX], jnp.int32)
    row = _row(fam)
    extent = (jnp.int32(len(PROMPT)),) + (
        (jnp.int32(len(SUFFIX)),) if row else ())
    return {
        "decode_step_paged": lambda p, c: fam.decode_step_paged(
            cfg, p, c, tokens, pos, tables),
        "prefill": lambda p, c: fam.paged_insert_prefill(
            c, *fam.paged_prefill_kv(cfg, p, prompt), PAGE_IDS, PAGE, *row),
        "suffix_prefill": lambda p, c: fam.paged_prefill_suffix_kv(
            cfg, p, suffix, *fam.paged_gather_prefix(c, PAGE_IDS[:2], *row),
            *extent),
    }


def _decode_step_paged(fam, cfg, params, family):
    return jax.jit(_programs(family)["decode_step_paged"])(
        params, _pool_after_prefill(family))


def _suffix_prefill(fam, cfg, params, family):
    return jax.jit(_programs(family)["suffix_prefill"])(
        params, _pool_after_prefill(family))


def _static_generate(fam, cfg, params, family):
    engine = _Engine(FAMILIES[family][0], cfg, params)
    return engine.generate([PROMPT, SUFFIX], max_new_tokens=6)


PATHS = {"prefill": _prefill, "decode_step_paged": _decode_step_paged,
         "paged_prefill_suffix_kv": _suffix_prefill,
         "static_generate": _static_generate}


def _same(a, b) -> bool:
    return all(jax.tree.leaves(jax.tree.map(
        lambda x, y: np.array_equal(np.asarray(x), np.asarray(y)), a, b)))


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_served_tree_gives_the_float32_trees_output_bit_for_bit(family, path):
    _, fam = FAMILIES[family]
    cfg, full, served, _ = _trees(family)
    want = PATHS[path](fam, cfg, full, family)
    got = PATHS[path](fam, cfg, served, family)
    assert _same(want, got)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_tree_whose_float32_read_leaves_were_cast_too_differs(family):
    _, fam = FAMILIES[family]
    cfg, full, _, every = _trees(family)
    want, _ = _prefill(fam, cfg, full, family)
    got, _ = _prefill(fam, cfg, every, family)
    assert not np.array_equal(np.asarray(want), np.asarray(got))


# ------------------------------------------ what a program does to a leaf
# Operations that hand a weight on as it is (a layer's slice of a stack,
# a transposed table): the leaf is followed through them, and into the
# bodies of scans and nested programs, to the first one that reads it.
_PASSES_ON = {"slice", "squeeze", "dynamic_slice", "reshape", "transpose",
              "broadcast_in_dim", "copy", "copy_p"}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            if isinstance(item, jex_core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jex_core.Jaxpr):
                yield item


def _follow(jaxpr, tracked: dict, reads: dict) -> None:
    tracked = dict(tracked)
    for eqn in jaxpr.eqns:
        held = [(i, tracked[v]) for i, v in enumerate(eqn.invars)
                if isinstance(v, jex_core.Var) and v in tracked]
        if not held:
            continue
        prim = eqn.primitive.name
        subs = list(_sub_jaxprs(eqn))
        if prim == "convert_element_type":
            reads[held[0][1]].add(
                f"convert:{jnp.dtype(eqn.params['new_dtype']).name}")
        elif prim in _PASSES_ON and held[0][0] == 0:
            tracked[eqn.outvars[0]] = held[0][1]
        elif subs:
            for sub in subs:
                if len(sub.invars) != len(eqn.invars):
                    for _, leaf in held:
                        reads[leaf].add(f"opaque:{prim}")
                    continue
                _follow(sub, {sub.invars[i]: leaf for i, leaf in held}, reads)
        else:
            for _, leaf in held:
                reads[leaf].add(prim)


def _reads(fn, params, *rest) -> dict:
    """{leaf path: how the program `fn(params, *rest)` first reads it}:
    `convert:<dtype>` for a cast, else the reading operation's name."""
    closed = jax.make_jaxpr(fn)(params, *rest)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    reads = {p: set() for p in paths}
    _follow(closed.jaxpr,
            dict(zip(closed.jaxpr.invars[:len(paths)], paths)), reads)
    return reads


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_familys_table_says_what_its_bodies_read(family):
    """On the float32 tree: a leaf is cast to `cfg.dtype` wherever it is
    read if and only if the family's table leaves it out. A leaf
    wrongly listed, either way, fails here by its name."""
    _, fam = FAMILIES[family]
    _, full, _, _ = _trees(family)
    cache = _pool_after_prefill(family)
    reads: dict = {}
    for fn in _programs(family).values():
        for path, how in _reads(fn, full, cache).items():
            reads.setdefault(path, set()).update(how)
    for path, how in reads.items():
        leaf = path.split("'")[-2]
        assert how, (path, "is never read")
        assert not any(h.startswith("opaque:") for h in how), (path, how)
        if leaf in fam.READ_AT_FLOAT32:
            assert "convert:bfloat16" not in how, (path, how)
        else:
            assert how == {"convert:bfloat16"}, (path, how)


@pytest.mark.parametrize("program", ["decode_step_paged", "prefill",
                                     "suffix_prefill"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_program_casts_a_served_weight(family, program):
    """On the served tree no `convert_element_type` takes a weight that
    came in as an argument: the per-step casts are gone and stay gone."""
    _, _, served, _ = _trees(family)
    reads = _reads(_programs(family)[program], served,
                   _pool_after_prefill(family))
    cast = {path: how for path, how in reads.items()
            if any(h.startswith("convert:") for h in how)}
    assert not cast


# ------------------------------------------------------------ the loader
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_load_params_draws_in_float32_then_rounds(family):
    name, fam = FAMILIES[family]
    cfg, params = load_params(name, seed=3)
    drawn = jax.jit(lambda key: fam.init(cfg, key)["params"])(
        jax.random.key(3))
    want = served_params(drawn, cfg.dtype, fam.READ_AT_FLOAT32)
    assert jax.tree.map(lambda x: x.dtype, params) == jax.tree.map(
        lambda x: x.dtype, want)
    assert _same(want, params)
    held = weight_bytes(params)
    assert set(held) == {"bfloat16", "float32"}
    assert held["float32"] < 0.05 * held["bfloat16"]


def test_a_family_that_states_nothing_keeps_float32():
    _, params = load_params("t5_tiny", seed=0)
    assert set(weight_bytes(params)) == {"float32"}


def test_quantize_is_given_float32_and_gives_the_tree_it_gave():
    from polyaxon_tpu.serving.quantize import quantize_tree

    cfg, got = load_params("llama_tiny", seed=0, quantize="int8")
    want = quantize_tree(jax.jit(
        lambda key: llama.init(cfg, key)["params"])(jax.random.key(0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert _same(want, got)
    assert got["layers"]["wq"].dtype == np.float32   # what it dequantizes to
    assert got["final_norm"].dtype == jnp.float32


def test_restore_rounds_on_the_host(tmp_path):
    import orbax.checkpoint as ocp

    cfg = llama.CONFIGS["llama_tiny"]
    full = llama.init(cfg, jax.random.key(5))["params"]
    with ocp.CheckpointManager(str(tmp_path / "ck")) as mgr:
        mgr.save(0, args=ocp.args.StandardSave({"params": full}))
        mgr.wait_until_finished()
    _, restored = load_params("llama_tiny", str(tmp_path / "ck"))
    assert _same(served_params(full, cfg.dtype, llama.READ_AT_FLOAT32),
                 restored)
    assert restored["layers"]["wq"].dtype == jnp.bfloat16
    assert restored["final_norm"].dtype == jnp.float32


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_load_params_under_a_mesh_keeps_dtypes_and_shardings(family):
    from polyaxon_tpu.parallel import build_mesh, rules_for_mesh
    from polyaxon_tpu.parallel.sharding import tree_shardings

    name, fam = FAMILIES[family]
    mesh = build_mesh(axes={"tp": 2}, devices=jax.devices()[:2])
    cfg, sharded = load_params(name, seed=0, mesh=mesh)
    _, single = load_params(name, seed=0)
    assert jax.tree.map(lambda x: x.dtype, sharded) == jax.tree.map(
        lambda x: x.dtype, single)
    want = tree_shardings(fam.logical_axes(cfg)["params"], mesh,
                          rules_for_mesh(mesh))
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, sh: x.sharding.is_equivalent_to(sh, x.ndim),
        sharded, want)))
    assert _same(single, sharded)


# -------------------------------------------------------------- /v1/stats
@pytest.mark.parametrize("engine", ["static", "continuous"])
def test_stats_weight_bytes_sums_to_the_tree_and_names_both_dtypes(engine):
    cfg, params = load_params("llama_tiny", seed=0)
    if engine == "static":
        stats = _Engine("llama_tiny", cfg, params).stats()
    else:
        from polyaxon_tpu.serving.batching import ContinuousBatchingEngine

        eng = ContinuousBatchingEngine(
            "llama_tiny", cfg, params, slots=2, max_len=32, kv="paged",
            page_size=4)
        try:
            stats = eng.stats()
        finally:
            eng.stop()
    held = stats["weight_bytes"]
    assert sum(held.values()) == tree_bytes(params)
    assert set(held) == {"bfloat16", "float32"}
    assert held["float32"] == 4 * (
        2 * cfg.n_layers * cfg.dim + cfg.dim)          # the norm gains


def test_stats_weight_bytes_of_an_int8_tree():
    _, params = load_params("llama_tiny", seed=0, quantize="int8")
    held = weight_bytes(params)
    assert set(held) == {"float32", "int8"}
    assert sum(held.values()) == tree_bytes(params)
