"""AOT compiles for the chip this sandbox does not have.

The TPU compiler is installed here and compiles for a DESCRIBED v5e:2x2
topology (guide `on-chip-measurement` §2): every jitted program of
chip_smoke.py's main path that has a TPU-only choice in it, at its real
size, with that choice passed explicitly — `jax.default_backend()` sees the
CPU during such a compile. What the chip's compiler would refuse (a Mosaic
tiling error, a program that does not fit 16 GB, a kernel that cannot be
partitioned) fails here, at no chip time. A compile that passes is not a
chip run.

All in this one file, topology described inside a module-scoped fixture,
nothing built at import, no child processes (only one process may hold the
TPU library), persistent compile cache off around the compiles.
"""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 << 30            # one v5e chip
ROWS, N_DIMS = 1 << 18, 1 << 22  # chip_smoke's fit: 262,144-row chunks, 2^22
BENCH_DIMS = 1 << 29             # the benchmark's Criteo table (PERF.md §4)
MESH_DIMS = 1 << 30              # ... and the one a (2,2) mesh holds
M = ROWS * 26                    # a chunk's occurrences (26 categoricals)
HIST_REAL = (1 << 20, 28, 3, 16, 32)   # (N, d, s, nodes, bins): HIGGS level


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled, budget=HBM_BYTES) -> int:
    """The program's own bytes on one device against its HBM (it does not
    count what else the process keeps there). `pytest -s` shows them."""
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"\n  args {m.argument_size_in_bytes:,} out "
          f"{m.output_size_in_bytes:,} temp {m.temp_size_in_bytes:,} alias "
          f"{m.alias_size_in_bytes:,} -> {total:,} bytes")
    assert total < budget, (total, m)
    return total


# --------------------------------------------------------------- histogram
def _hist_args(shape, sharding, trees=None):
    n, d, s, _nodes, _bins = shape
    lead = () if trees is None else (trees,)
    return (jax.ShapeDtypeStruct(lead + (n, d), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct(lead + (n, s), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct(lead + (n,), jnp.int32, sharding=sharding))


@pytest.mark.parametrize("shape", [
    (4096, 6, 3, 1, 32),      # the two cases the old on-TPU-only test never ran
    (4096, 6, 5, 4, 16),
    HIST_REAL,                # GBT ([g, h, w] stats) at level 4
    (1 << 20, 28, 3, 1, 32),  # ... at the root
    (1 << 20, 28, 2, 16, 32),  # RF (two class counts)
    (1 << 20, 28, 3, 64, 64),  # deeper and finer: the one-hot's VMEM block
], ids=["n4096-nodes1", "n4096-nodes4", "higgs-level", "higgs-root",
        "higgs-rf", "higgs-nodes64-bins64"])
def test_hist_pallas_compiles(one_chip, shape):
    from orange3_spark_tpu.ops.histogram import _hist_pallas

    compiled = _hist_pallas.lower(
        *_hist_args(shape, one_chip), nodes=shape[3], n_bins=shape[4]
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_hist_pallas_vmapped_forest_compiles(one_chip):
    """The forest's shape: grow_tree vmapped over 20 trees. Its transposed
    [T, d, N] key copy is the temp that grows linearly in rows (3.0 GB here,
    ~32 GB at HIGGS-11M: the reach work starts from this number)."""
    from orange3_spark_tpu.ops.histogram import _hist_pallas

    nodes, bins = HIST_REAL[3:]
    f = jax.jit(jax.vmap(functools.partial(_hist_pallas, nodes=nodes,
                                           n_bins=bins)))
    compiled = f.lower(*_hist_args(HIST_REAL, one_chip, trees=20)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes > 2 << 30


def test_grow_tree_level_compiles_with_kernel_inside(one_chip, monkeypatch):
    """One level of the growth loop with the Pallas kernel in it: the choice
    `node_histograms` makes on a TPU, steered here in the test."""
    from orange3_spark_tpu.models import _tree
    from orange3_spark_tpu.ops.histogram import _hist_pallas

    monkeypatch.setattr(_tree, "node_histograms", _hist_pallas)
    n, d, s, _nodes, bins = HIST_REAL
    B, S, _ = _hist_args(HIST_REAL, one_chip)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = _tree.grow_tree.lower(
        B, S, sds((d, bins - 1), jnp.float32), sds((1, d), jnp.float32),
        sds((), jnp.float32), depth=1, n_bins=bins, gain_mode="newton",
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


# ------------------------------------------------------------ hashed linear
@pytest.fixture(scope="module")
def hashed(session):
    """chip_smoke's estimator, its fresh fit state and one encoded zero
    chunk — concrete on the CPU; the tests turn them into shapes placed on
    the described chip. 'sort' is what resolve_sparse_lowering returns on a
    TPU (it sees the CPU here)."""
    import chip_smoke
    from orange3_spark_tpu.models.hashed_linear import (
        _encode_chunk_np, _init_fit_state,
    )

    p = chip_smoke.make_estimator(chip_smoke.REAL).params
    assert (p.chunk_rows, p.n_dims) == (ROWS, N_DIMS)
    theta, opt, salts_np, _salts, kw = _init_fit_state(p, session)
    assert kw["codec"] is not None and kw["codec"].mode == "packed"
    kw["sparse_lowering"] = "sort"
    chunk = _encode_chunk_np(
        kw["codec"], np.zeros((ROWS, 1 + p.n_dense + p.n_cat), np.float32),
        salts_np)
    return p, theta, opt, salts_np, chunk, kw


def _step_args(hashed, state_sh, row_sh, vec_sh, *, stack: int = 0):
    """Abstract arguments of `_hashed_step` (`stack` > 0: of the replay's
    chunk stack, one-chip shardings only)."""
    p, theta, opt, salts_np, chunk, kw = hashed
    lead = (stack,) if stack else ()

    def data(a):
        return jax.ShapeDtypeStruct(
            lead + a.shape, a.dtype,
            sharding=row_sh if a.ndim == 2 else vec_sh)

    scalar = functools.partial(jax.ShapeDtypeStruct, sharding=state_sh)
    return (
        _abstract(theta, state_sh), _abstract(opt, state_sh),
        jax.tree.map(data, chunk),
        scalar(lead, jnp.int32),                       # n_valid
        scalar(lead + (1,), jnp.float32),              # y (label_in_chunk)
        scalar(lead + (1,), jnp.float32),              # w
        _abstract(salts_np, state_sh),
        scalar((), jnp.float32), scalar((), jnp.float32),   # reg, lr
    ), kw


def _at_dims(hashed, n_dims: int):
    """`hashed` with the table (and every per-row optimizer array) at
    `n_dims` rows — shapes only, nothing of that size is allocated here —
    and the chunk re-encoded at the index width that table needs."""
    from orange3_spark_tpu.models.hashed_linear import _encode_chunk_np

    p, theta, opt, salts_np, chunk, kw = hashed
    if n_dims == p.n_dims:
        return hashed

    def grown(a):
        return jax.ShapeDtypeStruct(
            (n_dims,) + a.shape[1:] if a.shape[:1] == (p.n_dims,)
            else a.shape, a.dtype)

    codec = dataclasses.replace(kw["codec"], n_dims=n_dims)
    chunk = _encode_chunk_np(
        codec, np.zeros((ROWS, 1 + p.n_dense + p.n_cat), np.float32),
        salts_np)
    return (p, jax.tree.map(grown, theta), jax.tree.map(grown, opt),
            salts_np, chunk, {**kw, "n_dims": n_dims, "codec": codec})


def _no_occurrence_gather(text: str):
    """A chunk's M occurrences are reached through sorts, never through
    an M-index gather (optim/sparse.py; on the chip 12 ms against 49 for
    a permutation, 92 for the forward's read out of the 2 GB table): the
    program's gathers are the block loops' — the forward's read of the
    distinct rows, the update's of the rule's slots and the last-seen
    steps, ``SLOT_BLOCK`` indices each — and no other: none has an
    [M]-long or [rows, 26] result."""
    shapes = re.findall(r"= \w+\[([\d,]*)\][^ ]* gather\(", text)
    assert shapes
    for dims in shapes:
        assert np.prod([int(d) for d in dims.split(",") if d]) <= 1 << 20, (
            shapes)


def _tables_stay_in_place(compiled, n_dims: int):
    """The sparse step's three tables (weight, Adagrad accumulator,
    last-seen step) are donated, carried through the block loop of
    optim/sparse.py and written in place: no copy of a table's shape in
    the program, and — at the benchmark's size, where one table is 2.1 GB
    and everything sized by a chunk's 6.8M occurrences a few hundred MB —
    less temp than ONE table."""
    text = compiled.as_text()
    assert " while(" in text                   # the block loop is there
    _no_occurrence_gather(text)
    assert not re.search(rf"= \w+\[{n_dims}[,\]][^ ]* copy\(", text)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 3 * 4 * n_dims
    if n_dims == BENCH_DIMS:
        assert m.temp_size_in_bytes < 4 * n_dims, m.temp_size_in_bytes


@pytest.mark.parametrize("n_dims", [N_DIMS, BENCH_DIMS],
                         ids=["smoke-2^22", "bench-2^29"])
def test_hashed_step_sort_lowering_compiles(one_chip, hashed, n_dims):
    from orange3_spark_tpu.models.hashed_linear import _hashed_step

    args, kw = _step_args(_at_dims(hashed, n_dims), one_chip, one_chip,
                          one_chip)
    compiled = _hashed_step.donated.lower(*args, **kw).compile()
    _fits(compiled)
    _tables_stay_in_place(compiled, n_dims)


def _sorts_in_loops_over_tables(text: str, table_rows: int) -> list:
    """How many ``sort`` instructions each ``while`` body whose carried
    tuple holds a table of ``table_rows`` rows runs, itself or through what
    it calls — the replay's epoch scan and everything under it (chunk scan,
    step, block loop). The loop that builds the chunks' sort keys ahead of
    the scan carries the chunk stack and no table, so it is not among
    them."""
    comps = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \()", text):
        m = re.match(r"(?:ENTRY )?%([\w.-]+) \((.*)", block)
        if m:
            comps[m.group(1)] = (m.group(2), block)

    @functools.lru_cache(maxsize=None)
    def sorts(name):
        body = comps[name][1]
        called = set(re.findall(
            r"(?:body|condition|calls|to_apply)=%([\w.-]+)", body))
        return len(re.findall(r" sort\(", body)) + sum(
            sorts(c) for c in called if c in comps and c != name)

    bodies = set(re.findall(r" while\(.*?body=%([\w.-]+)", text))
    return [sorts(b) for b in sorted(bodies)
            if re.search(rf"\[{table_rows}[,\]]",
                         comps[b][0].split("\n")[0])]


@pytest.mark.parametrize("n_dims, hoist", [
    (N_DIMS, True), (BENCH_DIMS, True), (N_DIMS, False)],
    ids=["smoke-2^22", "bench-2^29", "smoke-2^22-sort-in-step"])
def test_hashed_replay_epochs_compiles(one_chip, hashed, n_dims, hoist):
    """The library-default one-dispatch replay: 7 epochs over 6 cached
    chunks in ONE program (chip_smoke's fit: 8 chunks less 2 held out),
    with every chunk's sort keys built once ahead of the epoch scan
    (``hoist_keys``, what a fit whose cache budget holds them runs) — and
    as a fit with a tight budget runs it, the sort inside each step.

    By hand at 2^29 (PR 36, this sandbox; PR 31 read temp 1,001,020,928
    hoisted, PR 29 1,055,901,696): args 6,645,879,296 / temp
    1,340,952,576 / alias 6,442,454,016 bytes — 0.843 GB of it the
    stacked keys (five i32 vectors a chunk, lane-tiled [6, M/128, 128]:
    stacked [6, M] the TPU's (8, 128) tiling pads 6 chunks to 8); five
    sorts in the program: the key sort, the sort that inverts its
    permutation and the one that compacts the segments' first places and
    table rows (``uniq``, ``head``: where an unsorted scatter and ITS
    sort stood) in the loop that builds the keys, and TWO under the epoch
    scan — the sort that carries the forward's row bits from sorted order
    to the occurrences and the sort that carries a step's per-occurrence
    gradients the other way. ``_hashed_step`` at 2^29: temp 274,322,944
    (PR 31: 303,973,376)."""
    from orange3_spark_tpu.models.hashed_linear import _hashed_replay_epochs

    (theta, opt, X, nv, y, w, salts, reg, lr), kw = _step_args(
        _at_dims(hashed, n_dims), one_chip, one_chip, one_chip, stack=6)
    compiled = _hashed_replay_epochs.donated.lower(
        theta, opt, (X, nv, y, w), salts, reg, lr, n_epochs=7,
        hoist_keys=hoist, **kw
    ).compile()
    _fits(compiled)
    _tables_stay_in_place(compiled, n_dims)
    text = compiled.as_text()
    in_scan = _sorts_in_loops_over_tables(text, n_dims)
    assert in_scan and text.count(" sort(") >= 3
    # a step under the epoch scan runs exactly two sorts, the forward's
    # carrier and the gradients', where the keys were hoisted; with the
    # sort in the step it runs the key half's three as well
    assert max(in_scan) == 2 if hoist else max(in_scan) == 5


def test_hashed_predict_compiles_at_bucket(one_chip, hashed):
    from orange3_spark_tpu.models.hashed_linear import _hashed_predict

    p, theta, _opt, salts_np, _chunk, _kw = hashed
    compiled = _hashed_predict.lower(
        _abstract(theta, one_chip),
        jax.ShapeDtypeStruct((4096, p.n_dense + p.n_cat), jnp.float32,
                             sharding=one_chip),
        _abstract(salts_np, one_chip), n_dims=p.n_dims, n_dense=p.n_dense,
    ).compile()
    _fits(compiled)


def test_hashed_step_compiles_on_four_chip_mesh(topo, hashed):
    """chip_smoke --four-chips' data-parallel step: rows on `data`, the
    table replicated (DataParallelPartitioner's (4,1) mesh).
    memory_analysis() is per device. The (2,2) model-sharded table of
    SPMDPartitioner is the next test, at the benchmark's own size."""
    from orange3_spark_tpu.models.hashed_linear import _hashed_step

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    args, kw = _step_args(hashed, NamedSharding(mesh, P()),
                          NamedSharding(mesh, P("data", None)),
                          NamedSharding(mesh, P("data")))
    compiled = _hashed_step.donated.lower(*args, **kw).compile()
    _fits(compiled)
    # the cross-device gradient sum the compiler had to put in
    assert "all-reduce" in compiled.as_text()


def _spmd_args(hashed, mesh, *, stack: int = 0):
    """`_step_args` as SPMDPartitioner(model_parallel=2) places them: the
    three tables' rows over `model`, chunk rows over `data`, the rest
    replicated (`stack` > 0: the replay's chunk stack)."""
    rep = NamedSharding(mesh, P())
    lead = (None,) if stack else ()
    (theta, opt, X, *rest), kw = _step_args(
        hashed, rep, NamedSharding(mesh, P(*lead, "data", None)),
        NamedSharding(mesh, P(*lead, "data")), stack=stack)
    n_dims = kw["n_dims"]

    def table(a):
        if a.shape[:1] != (n_dims,):
            return a
        spec = P("model", None) if len(a.shape) == 2 else P("model")
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    return (jax.tree.map(table, theta), jax.tree.map(table, opt), X,
            *rest), kw


def _tables_stay_sharded(compiled, n_dims: int):
    """On the (2,2) mesh a device holds HALF of each table and never a whole
    one: the program's arguments are 3 x 4 B x n_dims / 2 and a little, its
    temp is far under one half table, no operation has a result of the
    table's whole shape (an all-gather of one would be 4.3 GB at 2^30),
    and what is all-gathered over `data` is a chunk's 6.8M occurrence
    indices (ahead of the key sort) and its 6.8M per-occurrence gradients
    (27 MB, ahead of the sort that carries them to sorted order: a sort
    along an axis wants it whole) and nothing else; no all-reduce is
    M-long. The gathers and write-backs are per-shard masked lookups
    whose partial rows an all-reduce over `model` adds up; the forward's
    delta scatter, prefix sum and carrier sort run whole on every chip."""
    text = compiled.as_text()
    m = compiled.memory_analysis()
    half = 3 * 4 * n_dims // 2
    assert half <= m.argument_size_in_bytes < half + (256 << 20), m
    assert m.alias_size_in_bytes >= half
    assert m.temp_size_in_bytes < 4 * n_dims // 2, m.temp_size_in_bytes
    assert not re.search(rf"= \(?\w+\[{n_dims}[,\]]", text)
    gathered = re.findall(r"= (\w+\[[\d,]*\])\S* all-gather(?:-start)?\(",
                          text)
    assert gathered and set(gathered) <= {f"s32[{M}]", f"f32[{M}]"}, gathered
    assert " all-reduce(" in text and " while(" in text
    assert not re.search(rf"\[{M}[,\]][^=]* all-reduce(?:-start)?\(", text)
    _no_occurrence_gather(text)


def test_hashed_step_compiles_model_sharded_at_2_30(topo, hashed):
    """The benchmark's four-chip cell (criteo_svc_h30_fit_replay8_2x2): the
    'sort' step at 2^30 rows on SPMDPartitioner's (2,2) mesh, compiled by
    GSPMD from the arguments' shardings alone. ~80 s here."""
    from orange3_spark_tpu.models.hashed_linear import _hashed_step

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    args, kw = _spmd_args(_at_dims(hashed, MESH_DIMS), mesh)
    compiled = _hashed_step.donated.lower(*args, **kw).compile()
    _fits(compiled)
    _tables_stay_sharded(compiled, MESH_DIMS)


@pytest.mark.slow
def test_hashed_replay_epochs_compiles_model_sharded_at_2_30(topo, hashed):
    """The same cell's one-dispatch replay (7 epochs x 6 chunks), the
    chunks' sort keys built ahead of the epoch scan. Slow (~85 s more in
    this file's one worker); by hand (PR 28, this sandbox, sort in the
    step): args 6,569,333,248 / temp 178,806,272 / alias 6,442,454,016
    bytes a device, the step's six collectives and no other, no operation
    of the table's whole shape. PR 29, keys hoisted (replicated: 0.503 GB a
    device): temp 774,028,288, everything else as it was. PR 36 (five key
    vectors, 0.843 GB a device): temp 1,139,561,984; the forward's read of
    the distinct rows is one more [SLOT_BLOCK]-row all-reduce over `model`
    where the [131072, 26]-row one stood, and its carrier sort's result
    reaches the `data` shards through one all-to-all of 13.6 MB."""
    from orange3_spark_tpu.models.hashed_linear import _hashed_replay_epochs

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    (theta, opt, X, nv, y, w, salts, reg, lr), kw = _spmd_args(
        _at_dims(hashed, MESH_DIMS), mesh, stack=6)
    compiled = _hashed_replay_epochs.donated.lower(
        theta, opt, (X, nv, y, w), salts, reg, lr, n_epochs=7,
        hoist_keys=True, **kw
    ).compile()
    _fits(compiled)
    _tables_stay_sharded(compiled, MESH_DIMS)
    in_scan = _sorts_in_loops_over_tables(compiled.as_text(), MESH_DIMS // 2)
    assert in_scan and max(in_scan) == 2         # the two carriers, a step


# ------------------------------------------------------------------- kmeans
@pytest.fixture(scope="module")
def one_chip_rows(topo):
    """The rows of a one-chip session's table (``KMeans`` passes its
    session's row sharding to ``_lloyd``)."""
    return NamedSharding(Mesh(np.asarray(topo.devices[:1]), ("data",)),
                         P("data", None))


def test_kmeans_lloyd_compiles(one_chip, one_chip_rows):
    from orange3_spark_tpu.models.kmeans import _lloyd

    n, d, k = 2_097_152, 8, 10
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = _lloyd.donated.lower(
        sds((n, d), jnp.float32), sds((n,), jnp.float32),
        sds((k, d), jnp.float32), sds((), jnp.float32),
        k=k, max_iter=10, rows=one_chip_rows).compile()
    _fits(compiled)


def test_kmeans_lloyd_row_sharded_compiles_on_four_chip_mesh(topo):
    """KMeans on four chips at the canvas cell's shape (2^27 rows a chip):
    each chip's kernel passes its own rows inside ``shard_map`` and the
    partials are all-reduced; nothing gathers the table to one chip."""
    from orange3_spark_tpu.models.kmeans import _lloyd

    n, d, k = 1 << 29, 4, 10
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data", None))
    rep = functools.partial(jax.ShapeDtypeStruct,
                            sharding=NamedSharding(mesh, P()))
    compiled = _lloyd.donated.lower(
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.float32,
                             sharding=NamedSharding(mesh, P("data"))),
        rep((k, d), jnp.float32), rep((), jnp.float32),
        k=k, max_iter=20, rows=rows).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert "all-gather" not in text
    assert f"[{n},{d}]" not in text and f"[{n // 4},{d}]" in text


@pytest.mark.parametrize("n, d, k", [(1 << 27, 4, 10), (1 << 20, 16, 64),
                                     (1 << 20, 16, 4)],
                         ids=["cell-vpu", "over-threshold-mxu", "wide-mxu"])
def test_kmeans_lloyd_pass_by_shape(one_chip, one_chip_rows, n, d, k):
    """At the canvas cell's shapes an iteration is the vector-unit kernel:
    no matmul and no ``[N, k]`` buffer (distances, one-hot) anywhere in
    the program. Over ``VPU_MAX_DK``, or ``VPU_MAX_D`` alone, the MXU's
    cross term is compiled."""
    from orange3_spark_tpu.models.kmeans import _lloyd, _on_vpu

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = _lloyd.donated.lower(
        sds((n, d), jnp.float32), sds((n,), jnp.float32),
        sds((k, d), jnp.float32), sds((), jnp.float32),
        k=k, max_iter=20, rows=one_chip_rows).compile()
    text = compiled.as_text()
    matmul = re.search(r" (dot|convolution)\(", text) is not None
    if (d, k) == (4, 10):
        assert _on_vpu(d, k)
        assert not matmul and "tpu_custom_call" in text
        assert f"[{n},{k}]" not in text
        _fits(compiled)
    else:
        assert not _on_vpu(d, k)
        assert matmul and "tpu_custom_call" not in text


# ------------------------------------------------------------------- canvas
def test_staged_canvas_refit_compiles_at_the_cells_size(one_chip):
    """The benchmark's canvas (scaler -> PCA(4) -> KMeans(10),
    ``stage_graph(refit=True)``) at the cell's 2^27 x 8: the ONE program
    fits the chip beside nothing but its table, and holds none of what
    made it unusable there (PERF.md section 6, PR 35): no copy of the table
    by a column take (4,096 gathers and minutes of compile), no sort of
    all N rows for the seeding's sample, no scatter-add of N values for
    the cluster sizes, and the row contractions as blocked products. The
    canvas is built in a one-device session, as the cell's one chip is:
    KMeans passes each device's rows under the session's mesh."""
    from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
    from orange3_spark_tpu.workflow.graph import WorkflowGraph
    from orange3_spark_tpu.workflow.staging import stage_graph

    n = 1 << 27
    domain = Domain([ContinuousVariable(f"c{i}") for i in range(8)])
    head = np.random.default_rng(0).standard_normal((1024, 8))
    g = WorkflowGraph()
    session = TpuSession(Mesh(np.asarray(jax.devices()[:1]), ("data",)))
    src = g.add(OWTable(TpuTable.from_numpy(domain, head, session=session)))
    sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](with_mean=True))
    pca = g.add(WIDGET_REGISTRY["OWPCA"](k=4))
    km = g.add(WIDGET_REGISTRY["OWKMeans"](k=10, max_iter=20))
    g.connect(src, "data", sc, "data")
    g.connect(sc, "data", pca, "data")
    g.connect(pca, "data", km, "data")
    staged = stage_graph(g, km, refit=True)
    assert staged.refit_fallbacks == []
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = staged._plain.lower(
        (sds((n, 8), jnp.float32), None, sds((n,), jnp.float32))).compile()
    total = _fits(compiled)
    assert total < 14 << 30, total      # 13.0 GB read, 16.9 on the chip
    text = compiled.as_text()
    assert text.count(" gather(") < 16, text.count(" gather(")
    assert not re.search(r" sort\([^)]*\[134217728\]", text)
    assert not re.search(r"= \S+\[10\]\S* scatter\(", text)
    assert "8192,8,8" in text                  # the Gramian's blocked partials
    # KMeans' iterations are the vector-unit kernel, not rows_dot's products
    assert "tpu_custom_call" in text and "8192,10,4" not in text
