"""A server holds its weights in the compute dtype (`serving/server.py
load_params`, `models/common.py served_params`, each family's
`READ_AT_FLOAT32`): the cast that every decode and prefill program
repeated runs once at load. These tests hold the claim that nothing
served changes (bit for bit), that each family's table says what its
bodies do, and that the casts do not come back. The same for the
layout: a server holds the projections a family names in its
`HELD_TRANSPOSED` as `[N, D]` under `<name>_t` (`common.project` reads
either), and the tree held so gives the plain tree's outputs bit for
bit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from polyaxon_tpu.models import (lfm2, llama, moe, nemotron_h, qwen3_next,
                                 smallthinker)
from polyaxon_tpu.models.common import (HELD_TRANSPOSED_SUFFIX,
                                        hold_transposed, served_params)
from polyaxon_tpu.serving.quantize import (held_transposed_bytes, tree_bytes,
                                           weight_bytes)
from polyaxon_tpu.serving.server import _Engine, load_params

FAMILIES = {"llama": ("llama_tiny", llama), "moe": ("moe_tiny", moe),
            "lfm2": ("lfm2_tiny", lfm2),
            "nemotron_h": ("nemotron_h_tiny", nemotron_h),
            "qwen3_next": ("qwen3_next_tiny", qwen3_next),
            "smallthinker": ("smallthinker_tiny", smallthinker)}
# The trees a server may hold: every leaf `[D, N]` as drawn, or the
# family's `HELD_TRANSPOSED` swapped (what `load_params` gives; the same
# tree for a family that names none).
TREES = ("plain", "held")
PAGE = 4
N_PAGES = 8
PROMPT = [5, 6, 7, 1, 2, 3, 4, 9]          # two whole pages
SUFFIX = [8, 2, 11, 3]
PAGE_IDS = jnp.asarray([1, 2, 3, -1], jnp.int32)


def _row(fam) -> tuple:
    """What a prefill program is told beside the block table by a
    family whose cache has per-row leaves: the engine's row."""
    return (jnp.int32(0),) if hasattr(fam, "paged_init_rows") else ()


def _windowed(fam) -> bool:
    """A family with a second page space (``paged_window``): its pool
    is told that space's size, its programs both block tables (here the
    same pages in both: PROMPT lies inside the window), and it has no
    suffix prefill."""
    return hasattr(fam, "paged_window")


def _pool(fam, cfg) -> dict:
    if _windowed(fam):
        return fam.paged_init_cache(cfg, N_PAGES, PAGE, N_PAGES)
    cache = fam.paged_init_cache(cfg, N_PAGES, PAGE)
    if hasattr(fam, "paged_init_rows"):
        cache["rows"] = fam.paged_init_rows(cfg, 2)
    return cache


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _held(fam) -> frozenset:
    return getattr(fam, "HELD_TRANSPOSED", frozenset())


@functools.lru_cache(maxsize=None)
def _trees(family: str):
    """(cfg, the float32 tree, the served trees by `TREES`' names, the
    tree with every leaf cast). The float32-read leaves are drawn anew:
    a gain of exactly 1 rounds to itself and would hide a leaf wrongly
    cast."""
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
    served = {
        "plain": served_params(full, cfg.dtype, fam.READ_AT_FLOAT32),
        "held": served_params(full, cfg.dtype, fam.READ_AT_FLOAT32,
                              _held(fam))}
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
    return jax.jit(_programs(family)["prefill"])(full, _pool(fam, cfg))


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
    if _windowed(fam):
        return {
            "decode_step_paged": lambda p, c: fam.decode_step_paged(
                cfg, p, c, tokens, pos, (tables, tables)),
            "prefill": lambda p, c: fam.paged_insert_prefill(
                c, *fam.paged_prefill_kv(cfg, p, prompt),
                jnp.stack([PAGE_IDS, PAGE_IDS]), PAGE)}
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
# Every (family, program) there is: a family with a window space has no
# suffix prefill.
FAMILY_PROGRAMS = [
    (family, program) for family in sorted(FAMILIES)
    for program in ("decode_step_paged", "prefill", "suffix_prefill")
    if not (program == "suffix_prefill" and _windowed(FAMILIES[family][1]))]
FAMILY_PATHS = [
    (family, path) for family in sorted(FAMILIES) for path in sorted(PATHS)
    if not (path == "paged_prefill_suffix_kv"
            and _windowed(FAMILIES[family][1]))]


def _same(a, b) -> bool:
    return all(jax.tree.leaves(jax.tree.map(
        lambda x, y: np.array_equal(np.asarray(x), np.asarray(y)), a, b)))


@pytest.mark.parametrize("tree", TREES)
@pytest.mark.parametrize("family,path", FAMILY_PATHS)
def test_served_tree_gives_the_float32_trees_output_bit_for_bit(
        family, path, tree):
    """Both served trees against the float32 one, so the tree with its
    projections held `[N, D]` gives the plain bfloat16 tree's logits and
    pools bit for bit: the decode step, the whole-prompt prefill, the
    suffix prefill and the static engine's generation."""
    _, fam = FAMILIES[family]
    cfg, _, served, _ = _trees(family)
    got = PATHS[path](fam, cfg, served[tree], family)
    assert _same(_float32_output(family, path), got)


@functools.lru_cache(maxsize=None)
def _float32_output(family: str, path: str):
    _, fam = FAMILIES[family]
    cfg, full, _, _ = _trees(family)
    return PATHS[path](fam, cfg, full, family)


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


@pytest.mark.parametrize("tree", TREES)
@pytest.mark.parametrize("family,program", FAMILY_PROGRAMS)
def test_no_program_casts_a_served_weight(family, program, tree):
    """On a served tree no `convert_element_type` takes a weight that
    came in as an argument: the per-step casts are gone and stay gone."""
    _, _, served, _ = _trees(family)
    reads = _reads(_programs(family)[program], served[tree],
                   _pool_after_prefill(family))
    cast = {path: how for path, how in reads.items()
            if any(h.startswith("convert:") for h in how)}
    assert not cast


# ------------------------------------------------------------- the layout
def test_served_params_with_three_arguments_transposes_nothing():
    """`benchmark/tools/aot_memory_rows.py` calls it so: the plain tree,
    key for key and shape for shape."""
    cfg, full, served, _ = _trees("llama")
    got = served_params(full, cfg.dtype, llama.READ_AT_FLOAT32)
    assert jax.tree.structure(got) == jax.tree.structure(full)
    assert jax.tree.map(lambda x: x.shape, got) == jax.tree.map(
        lambda x: x.shape, full)
    assert _same(got, served["plain"])
    assert held_transposed_bytes(got) == 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_held_tree_is_the_plain_tree_with_the_named_leaves_swapped(family):
    _, fam = FAMILIES[family]
    _, _, served, _ = _trees(family)
    want = hold_transposed(served["plain"], _held(fam),
                           lambda x: jnp.swapaxes(x, -1, -2))
    assert jax.tree.structure(want) == jax.tree.structure(served["held"])
    assert _same(want, served["held"])
    swapped = [path for path, _ in jax.tree_util.tree_flatten_with_path(
        served["held"])[0] if _leaf_name(path).endswith(
            HELD_TRANSPOSED_SUFFIX)]
    assert len(swapped) == len(_held(fam))


@pytest.mark.parametrize("family", sorted(
    f for f, (_, fam) in FAMILIES.items() if _held(fam)))
def test_the_held_table_names_leaves_the_accessor_reads(family):
    """Each name in a family's `HELD_TRANSPOSED` is a leaf `init` draws,
    none of them one the bodies read at float32, and on the held tree
    every program reads `<name>_t` (there is no `<name>` to fall back
    on) straight into a dot: no cast, no transpose of its own."""
    _, fam = FAMILIES[family]
    _, full, served, _ = _trees(family)
    names = {_leaf_name(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(full)[0]}
    assert _held(fam) <= names
    assert not _held(fam) & fam.READ_AT_FLOAT32
    cache = _pool_after_prefill(family)
    for program, fn in _programs(family).items():
        for path, how in _reads(fn, served["held"], cache).items():
            if path.split("'")[-2].endswith(HELD_TRANSPOSED_SUFFIX):
                assert how == {"dot_general"}, (program, path, how)


# ------------------------------------------------------------ the loader
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_load_params_draws_in_float32_then_rounds(family):
    name, fam = FAMILIES[family]
    cfg, params = load_params(name, seed=3)
    drawn = jax.jit(lambda key: fam.init(cfg, key)["params"])(
        jax.random.key(3))
    want = served_params(drawn, cfg.dtype, fam.READ_AT_FLOAT32, _held(fam))
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
    assert held_transposed_bytes(got) == 0           # and `[D, N]`


@pytest.mark.parametrize("mesh_axes", [None, {"tp": 2}])
def test_restore_rounds_and_swaps_on_the_host(tmp_path, mesh_axes):
    """A checkpoint's leaves are `[D, N]` float32 (what training saves):
    it is validated so and loads to the tree the draw of the same
    values gives, sharded or not."""
    import orbax.checkpoint as ocp

    from polyaxon_tpu.parallel import build_mesh

    cfg = llama.CONFIGS["llama_tiny"]
    full = llama.init(cfg, jax.random.key(5))["params"]
    with ocp.CheckpointManager(str(tmp_path / "ck")) as mgr:
        mgr.save(0, args=ocp.args.StandardSave({"params": full}))
        mgr.wait_until_finished()
    mesh = mesh_axes and build_mesh(axes=mesh_axes,
                                    devices=jax.devices()[:2])
    _, restored = load_params("llama_tiny", str(tmp_path / "ck"), mesh=mesh)
    _, drawn = load_params("llama_tiny", seed=5, mesh=mesh)
    assert jax.tree.structure(restored) == jax.tree.structure(drawn)
    assert _same(drawn, restored)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, y: x.sharding.is_equivalent_to(y.sharding, x.ndim),
        restored, drawn)))
    assert _same(served_params(full, cfg.dtype, llama.READ_AT_FLOAT32,
                               llama.HELD_TRANSPOSED), restored)
    assert restored["layers"]["wq_t"].dtype == jnp.bfloat16
    assert restored["layers"]["wq_t"].shape == (
        cfg.n_layers, cfg.n_heads * cfg.head_dim, cfg.dim)
    assert restored["final_norm"].dtype == jnp.float32


def test_a_checkpoint_saved_held_transposed_is_refused(tmp_path):
    """The on-disk format is `[D, N]` under the plain names: a tree that
    is not is refused by its structure, not served swapped twice."""
    import orbax.checkpoint as ocp

    cfg = llama.CONFIGS["llama_tiny"]
    _, held = load_params("llama_tiny", seed=5)
    with ocp.CheckpointManager(str(tmp_path / "ck")) as mgr:
        mgr.save(0, args=ocp.args.StandardSave({"params": held}))
        mgr.wait_until_finished()
    with pytest.raises(ValueError, match="structure differs"):
        load_params("llama_tiny", str(tmp_path / "ck"))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_load_params_under_a_mesh_keeps_dtypes_and_shardings(family):
    from polyaxon_tpu.parallel import build_mesh, rules_for_mesh
    from polyaxon_tpu.parallel.sharding import tree_shardings

    """Each held-transposed leaf gets the plain leaf's sharding with
    the last two axes swapped (a `wq` split over its heads stays split
    over its heads), every other leaf the plain leaf's own."""
    from jax.sharding import NamedSharding, PartitionSpec

    name, fam = FAMILIES[family]
    mesh = build_mesh(axes={"tp": 2}, devices=jax.devices()[:2])
    cfg, sharded = load_params(name, seed=0, mesh=mesh)
    _, single = load_params(name, seed=0)
    assert jax.tree.map(lambda x: x.dtype, sharded) == jax.tree.map(
        lambda x: x.dtype, single)
    plain = tree_shardings(fam.logical_axes(cfg)["params"], mesh,
                           rules_for_mesh(mesh))
    want = hold_transposed(
        plain, _held(fam), lambda sh: NamedSharding(mesh, PartitionSpec(
            *sh.spec[:-2], sh.spec[-1], sh.spec[-2])))
    assert jax.tree.structure(want) == jax.tree.structure(sharded)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda x, sh: x.sharding.is_equivalent_to(sh, x.ndim),
        sharded, want)))
    if _held(fam):
        split = [path for path, x in jax.tree_util.tree_flatten_with_path(
            sharded)[0] if _leaf_name(path).endswith(HELD_TRANSPOSED_SUFFIX)
            and not x.sharding.is_fully_replicated]
        assert split, "no held-transposed leaf is sharded over tp"
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
    # wq, wk, wv of every layer, bfloat16, held [N, D].
    assert stats["weights_held_transposed_bytes"] == 2 * cfg.n_layers * (
        cfg.dim * cfg.head_dim * (cfg.n_heads + 2 * cfg.n_kv_heads))


def test_stats_weight_bytes_of_an_int8_tree():
    _, params = load_params("llama_tiny", seed=0, quantize="int8")
    held = weight_bytes(params)
    assert set(held) == {"float32", "int8"}
    assert sum(held.values()) == tree_bytes(params)
    assert _Engine("llama_tiny", llama.CONFIGS["llama_tiny"],
                   params).stats()["weights_held_transposed_bytes"] == 0
