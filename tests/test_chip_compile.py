"""The served path's programs, compiled by the chip's own compiler.

`chip_smoke.py` runs them on a v5e; here the TPU compiler that is
installed beside JAX compiles the same programs, at the same shapes, for
a DESCRIBED `v5e:2x2` that is not attached — so a program the chip would
refuse (memory, layout, a collective it cannot partition) fails in
tier-1, at no chip time.  Nothing runs: these tests say nothing about
results or speed.

The topology is described inside a module-scoped fixture of THIS file
(never at import, in a skipif, in parametrize or in conftest.py): only
the xdist worker that is handed this file loads the TPU library.  All
compiles happen in the test's own process, with the persistent compile
cache off (conftest.py) — a described-device executable cannot be read
back.
"""

import re
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from chip_smoke import PHASES, served_cfg
from deneva_tpu.config import Config
from deneva_tpu.ops import gather as G

HBM_BYTES = 16 * 1024 ** 3      # one v5e chip (Google Cloud "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _with_sharding(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding
    for every leaf, or a matching pytree of them)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _counts_row_groups(cfg: Config) -> bool:
    """Whether the cell's server counts the row write's tile groups
    (PR 48: one whose workload writes full rows through
    `ops.scatter.scatter_winner_rows` — YCSB under ``sim_full_row``)."""
    from deneva_tpu.workloads import get_workload
    return getattr(get_workload(cfg), "writes_row_groups", False)


def _group_program(cfg: Config, monkeypatch):
    """(jitted C-epoch group, abstract args) exactly as `ServerNode`
    builds them, state and feed described instead of allocated."""
    from deneva_tpu.cc import get_backend
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.engine.epoch import make_dist_group
    from deneva_tpu.workloads import get_workload

    wl, be = get_workload(cfg), get_backend(cfg.cc_alg)
    k, _t, s = wl.to_wire(wl.generate(jax.random.PRNGKey(0), 1))
    width, n_scal = k.shape[1], s.shape[1]
    # make_dist_group donates off the CPU backend only, and asks
    # jax.default_backend() when it is built: answer as the chip would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    group = make_dist_group(cfg, wl, be, width, n_scal)
    state = {"db": jax.eval_shape(wl.load),
             "cc_state": jax.eval_shape(lambda: be.init_state(cfg)),
             "stats": jax.eval_shape(lambda: init_device_stats(
                 len(getattr(wl, "txn_type_names", ("txn",))),
                 row_groups=_counts_row_groups(cfg)))}
    n = cfg.pipeline_epochs * cfg.epoch_batch
    feed = (jax.ShapeDtypeStruct((n,), np.bool_),
            jax.ShapeDtypeStruct((n,), np.int32),
            jax.ShapeDtypeStruct((n * width,), np.int32),
            jax.ShapeDtypeStruct((n * width,), np.int8),
            jax.ShapeDtypeStruct((n * n_scal,), np.int32))
    return group, state, feed


def _compile(lowerable, *args):
    t0 = time.monotonic()
    compiled = lowerable.lower(*args).compile()
    return compiled, time.monotonic() - t0


def _report(name: str, compiled, secs: float) -> int:
    """Print the compiler's memory analysis; return the bytes one device
    must hold for this program (arguments + outputs + temporaries, less
    what donation aliases)."""
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"\n[chip-compile] {name}: compile_s={secs:.1f} "
          f"args={m.argument_size_in_bytes} out={m.output_size_in_bytes} "
          f"temp={m.temp_size_in_bytes} alias={m.alias_size_in_bytes} "
          f"code={m.generated_code_size_in_bytes} device_bytes={need}")
    return need


@pytest.mark.parametrize("name,over", [(p[0], p[1]) for p in PHASES],
                         ids=[p[0] for p in PHASES])
def test_served_group_compiles_for_v5e(name, over, one_chip, monkeypatch):
    """The C-epoch `lax.scan` group of `make_dist_group` at the shapes of
    each `chip_smoke.py` phase (TPU_BATCH 8M fingerprint, TPU_BATCH 2M
    full-row, OCC eb=1024): the chip's compiler accepts it, K=2 in-flight
    groups beside the resident table fit 16 GB, and donation aliases the
    state (the table is updated in place, not copied per group)."""
    cfg = served_cfg(**{k: v for k, v in over.items() if k != "logging"})
    group, state, feed = _group_program(cfg, monkeypatch)
    state, feed = _with_sharding((state, feed), one_chip)
    compiled, secs = _compile(group, state["db"], state["cc_state"],
                              state["stats"], *feed)
    need = _report(name, compiled, secs)
    m = compiled.memory_analysis()
    table = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"]))
    # the donated table comes back aliased: no second copy per group
    assert m.alias_size_in_bytes >= table
    # K in-flight groups share ONE resident state; each adds its own
    # feed, verdict planes and temporaries
    per_group = need - table
    assert table + cfg.pipeline_groups * per_group < HBM_BYTES


def _column_copies(hlo: str, shape: str) -> list[str]:
    """Where the compiled program copies an array of ``shape``: "entry"
    for the entry computation, "inner" for any other (a loop body, a
    conditional's branch: once an EPOCH or more)."""
    where = None
    copies = []
    for ln in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%[\w.\-]+ \(", ln)
        if head:
            where = "entry" if head.group(1) else "inner"
        if re.search(r"= " + re.escape(shape) + r"\S* copy\(", ln):
            copies.append(where)
    return copies


def _hlo_shape(x) -> str:
    """``x``'s shape as the compiled text writes it, without the layout."""
    names = {"int32": "s32", "float32": "f32", "uint8": "u8",
             "uint32": "u32"}
    return f"{names[np.dtype(x.dtype).name]}[{','.join(map(str, x.shape))}]"


def _row_gathers(hlo: str, width: int) -> set[int]:
    """Lanes of every row gather of ``width``-byte rows in the program."""
    return {int(m.group(1)) for m in re.finditer(
        r"= u8\[(\d+)," + str(width) + r"\]\S* gather\(", hlo)}


def _looped_row_gathers(hlo: str, width: int) -> list[tuple[int, list[str]]]:
    """(lanes, scopes of its op_name) of every row gather of ``width``-
    byte rows; each has to sit in a `while`'s body — itself, or the
    fusion that calls its computation."""
    bodies = set(re.findall(r" while\(.*?body=(%[\w.\-]+)", hlo))
    where, caller, found = None, {}, []
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(", ln)
        if head:
            where = head.group(1)
        for called in re.findall(r"calls=(%[\w.\-]+)", ln):
            caller[called] = where
        m = re.search(r"= u8\[(\d+)," + str(width) + r"\]\S* gather\(", ln)
        if m:
            name = re.search(r'op_name="([^"]*)"', ln).group(1)
            found.append((int(m.group(1)), name.split("/"), where, ln))
    for _, _, where, ln in found:
        assert where in bodies or caller.get(where) in bodies, ln
    return [f[:2] for f in found]


def _scatter_lanes(hlo: str, shape: str) -> list[int]:
    """Lanes of every scatter into an array of ``shape``: the rows of
    the scatter's index operand."""
    rows = {m.group(1): int(m.group(2)) for m in re.finditer(
        r"(%[\w.\-]+) = [a-z]\d+\[(\d+)(?:,\d+)*\]", hlo)}
    return sorted(rows[m.group(1)] for m in re.finditer(
        r"= " + re.escape(shape) + r"\S* scatter\(%[\w.\-]+, (%[\w.\-]+),",
        hlo))


def _row_write_kernels(hlo: str, shape: str) -> list[list[str]]:
    """Scopes of the `op_name` of every Pallas kernel that returns an
    array of ``shape``.  Each has to sit in a `while`'s body and alias
    its result to its operand of that shape (the column is written in
    place)."""
    bodies = set(re.findall(r" while\(.*?body=(%[\w.\-]+)", hlo))
    where, found = None, []
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(", ln)
        if head:
            where = head.group(1)
        if 'custom_call_target="tpu_custom_call"' not in ln or not \
                re.search(r"= " + re.escape(shape) + r"\S* custom-call\(", ln):
            continue
        assert where in bodies, ln[:300]
        operands = re.findall(r"[a-z]+\d+\[[\d,]*\]", re.search(
            r"operand_layout_constraints=\{(.*?\})\}", ln).group(1))
        aliased = int(re.search(
            r"output_to_operand_aliasing=\{\{\}: \((\d+), \{\}\)\}",
            ln).group(1))
        assert operands[aliased] == shape, ln[:300]
        found.append(re.search(r'op_name="([^"]*)"', ln).group(1).split("/"))
    return found


def _one_row_write_kernel(hlo: str, shape: str, lanes: int,
                          scope: str = "ep.write") -> None:
    """The row write of the hot cell's program since PR 48: ONE Pallas
    kernel in the loop of the conditional's `few` side under ``scope``,
    and no scatter into the column but the flagged whole pass of all
    ``lanes`` (XLA's one-row-at-a-time scatter of N / 64 lanes a trip
    went); the column is copied nowhere but in the entry computation."""
    (scopes,) = _row_write_kernels(hlo, shape)
    assert scopes[scopes.index(scope):][1:] == [
        "cond", "branch_1_fun", "while", "body", "pallas_call"], scopes
    assert _scatter_lanes(hlo, shape) == [lanes]
    # ... and the kernel's row-major operand costs no copy in the epoch
    assert set(_column_copies(hlo, shape)) <= {"entry"}


def _xlas_row_write_loop(hlo: str, shape: str, lanes: int) -> None:
    """The row write of a served program whose calls are shorter than
    `ops.scatter._MIN_CALL_LANES` (the medium cells: 160 lanes; a shard
    of four: 1,280), as at PRs 26-47: XLA's scatter of N / 64 lanes in
    the loop, the whole pass's of every lane, and no kernel."""
    assert _row_write_kernels(hlo, shape) == []
    assert _scatter_lanes(hlo, shape) == [lanes // 64, lanes]
    assert set(_column_copies(hlo, shape)) <= {"entry"}


@pytest.mark.parametrize("over", [
    dict(), dict(cc_alg="OCC", epoch_batch=1024, max_txn_in_flight=1 << 17,
                 client_batch_size=1024)], ids=["tpu_batch", "occ"])
def test_full_row_column_is_written_in_place_inside_the_epoch_scan(
        over, one_chip, monkeypatch):
    """The winners-only row scatter (`ops.scatter.scatter_winner_rows`)
    writes the column inside a loop inside a conditional inside the
    epoch scan.  With the epoch's gather left unordered against it the
    chip's compiler copied the whole column twice an EPOCH (my chip run,
    PR 26: 5 ms of copies around 2.3 ms of scatter; its `after` argument
    is the cure).  The only copies of the column are the entry
    computation's two relayouts, once a GROUP, as at the parent.

    Since PR 47 the forwarding executor's reads come out of a loop too
    (`ops.gather.checksum_needed_rows`), ahead of that write: ONE row
    gather of N / 64 lanes in a `while` body under `ep.read`, which
    closes over the column — the same pin holds with both loops in the
    program (OCC's masked path has no plan and gathers its 10,240 lanes
    in one call, as it did).

    Since PR 48 a trip of the forwarding executor's write loop (calls of
    2,560 lanes) is ONE Pallas kernel (`ops.scatter.
    write_rows_by_group`) under `ep.write` that aliases the column: no
    scatter of N / 64 lanes is left, only the whole pass's of every
    lane, and the column's copies are still the entry's two.  OCC's
    calls of 160 lanes keep XLA's scatter
    (`ops.scatter._MIN_CALL_LANES`: the kernel is compiled in at the one
    call length the chip has served it through)."""
    cfg = served_cfg(sim_full_row="true", synth_table_size=1 << 21, **over)
    group, state, feed = _group_program(cfg, monkeypatch)
    state, feed = _with_sharding((state, feed), one_chip)
    compiled, _ = _compile(group, state["db"], state["cc_state"],
                           state["stats"], *feed)
    f0 = state["db"]["MAIN_TABLE"].columns["F0"]
    hlo = compiled.as_text()
    copies = _column_copies(hlo, f"u8[{f0.shape[0]},{f0.shape[1]}]")
    assert copies and set(copies) == {"entry"}, copies
    lanes = cfg.epoch_batch * cfg.req_per_query
    shape = f"u8[{f0.shape[0]},{f0.shape[1]}]"
    if cfg.cc_alg == "TPU_BATCH":
        _one_row_write_kernel(hlo, shape, lanes)
    else:
        _xlas_row_write_loop(hlo, shape, lanes)
    if cfg.cc_alg == "TPU_BATCH":
        ((got, scopes),) = _looped_row_gathers(hlo, f0.shape[1])
        assert got == -(-lanes // G._CHUNKS)
        assert scopes[scopes.index("ep.read"):][1:3] == ["while", "body"]
    else:
        assert re.findall(r"= u8\[([\d,]+),100\]\S* gather\(", hlo) == [
            f"{cfg.epoch_batch},{cfg.req_per_query}"]


def test_ycsb_loader_compiles_for_v5e(one_chip):
    """The YCSB loader as one program at the served 8M rows (on the chip
    it runs op by op; one program bounds what any of its ops needs)."""
    from deneva_tpu.workloads import get_workload
    wl = get_workload(served_cfg())
    compiled, secs = _compile(jax.jit(wl.load, out_shardings=one_chip))
    assert _report("ycsb_loader_8m", compiled, secs) < HBM_BYTES


def test_engine_step_compiles_for_v5e(one_chip):
    """`__graft_entry__.entry()`: one epoch step of the in-process
    engine (the layer under the served path, and `bench.py`'s)."""
    from __graft_entry__ import entry
    step, (state,) = entry()
    arg = _with_sharding(jax.eval_shape(lambda: state), one_chip)
    compiled, secs = _compile(jax.jit(step), arg)
    assert _report("engine_step", compiled, secs) < HBM_BYTES


def test_mesh_group_compiles_for_four_chips(topo, monkeypatch):
    """`device_parts=4`: the same group over a Mesh of the four described
    chips, state placed by `state_shardings`, feed replicated — the
    table must go in and come out SHARDED (a quarter per chip), and the
    owner exchange must lower to an all-to-all."""
    from deneva_tpu.parallel import mesh as M
    cfg = served_cfg(device_parts=4)
    mesh = Mesh(np.array(topo.devices[:4]), (M.AXIS,))
    group, state, feed = _group_program(cfg, monkeypatch)
    shardings = M.state_shardings(mesh, state)
    state = _with_sharding(state, shardings)
    feed = _with_sharding(feed, NamedSharding(mesh, P()))
    with M.use_mesh(mesh):
        compiled, secs = _compile(group, state["db"], state["cc_state"],
                                  state["stats"], *feed)
    need = _report("tpu_batch_8m_dp4 (per device)", compiled, secs)
    assert need < HBM_BYTES
    assert "all-to-all" in compiled.as_text()
    f0 = state["db"]["MAIN_TABLE"].columns["F0"]
    assert f0.sharding.spec == P(M.AXIS)
    assert f0.sharding.shard_shape(f0.shape) == (f0.shape[0] // 4,)
    out_db = compiled.output_shardings[0]
    assert out_db["MAIN_TABLE"].columns["F0"].spec == P(M.AXIS)
    # (TPU_BATCH keeps no cross-epoch watermark state — its cc_state is
    # empty — so the table is this deployment's only sharded leaf)


# ---- the four-chip cell (PR 29): ycsb_fullrow_tpubatch_dp4.hot -----------

CELL_DP4 = "ycsb_fullrow_tpubatch_dp4.hot"


def _cell_cfg(cell: str = CELL_DP4) -> Config:
    """The `Config` of a cell's timed launch, through the harness's own
    `load_cell` / `server_fields` (benchmark/run.py never imports JAX)."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "run.py")
    spec = importlib.util.spec_from_file_location("bench_run_for_compile",
                                                  path)
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    fields = run.server_fields(run.load_cell(cell), 3_000_000_019, {})
    return Config.from_args([f"--{k}={v}" for k, v in fields.items()]
                            ).replace(node_id=0, part_cnt=1)


@pytest.fixture(scope="module")
def dp4_cell(topo):
    """(cfg, mesh, state, compiled group, seconds) of the cell's group
    program — 25,165,824 full rows over the four described chips, epochs
    of 16,384, C=32 — compiled ONCE for the tests below."""
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.parallel import mesh as M
    cfg = _cell_cfg()
    mesh = Mesh(np.array(topo.devices[:4]), (M.AXIS,))
    with pytest.MonkeyPatch.context() as mp:
        group, state, feed = _group_program(cfg, mp)
        # the server of a forwarding backend on a mesh counts the
        # shard-epochs that ran the exchange's defer pass (PR 41)
        state["stats"] = jax.eval_shape(
            lambda: init_device_stats(1, mc_defer_passes=True,
                                      row_groups=True))
        state = _with_sharding(state, M.state_shardings(mesh, state))
        feed = _with_sharding(feed, NamedSharding(mesh, P()))
        with M.use_mesh(mesh):
            compiled, secs = _compile(group, state["db"], state["cc_state"],
                                      state["stats"], *feed)
    return cfg, mesh, state, compiled, secs


def test_sharded_loader_compiles_for_four_chips_at_the_cells_size(topo):
    """One column of the cell's table, built block by block on the chip
    that holds it (`storage.table.mc_column_builder`): each device is
    asked for its 6,291,520 rows x 100 B and nothing beside them — the
    ten columns of a shard then fit one chip with room for serving."""
    from deneva_tpu.parallel import mesh as M
    from deneva_tpu.storage.table import mc_block_geometry, mc_column_builder
    from deneva_tpu.workloads.ycsb import _field_bytes
    cfg = _cell_cfg()
    assert (cfg.synth_table_size, cfg.device_parts) == (25_165_824, 4)
    mesh = Mesh(np.array(topo.devices[:4]), (M.AXIS,))
    build = mc_column_builder(
        mesh, cfg.synth_table_size,
        lambda slot: _field_bytes(slot, 0, cfg.tup_size), np.uint8,
        (cfg.tup_size,))
    compiled, secs = _compile(build)
    need = _report("dp4_loader_column (per device)", compiled, secs)
    _, lb = mc_block_geometry(cfg.synth_table_size, 1, 4)
    block = lb * cfg.tup_size
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes >= block
    # the block and at most a few percent of scratch: no uint32[rows,100]
    # temporary, no second copy, nothing of another device's rows
    assert need < 1.1 * block
    (out,) = jax.tree.leaves(compiled.output_shardings)
    assert out.shard_shape((4 * lb, cfg.tup_size)) == (lb, cfg.tup_size)
    assert cfg.field_per_tuple * need < HBM_BYTES // 2


def test_dp4_cell_group_fits_each_of_the_four_chips(dp4_cell):
    cfg, mesh, state, compiled, secs = dp4_cell
    need = _report("dp4_cell_group (per device)", compiled, secs)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES
    shard = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"])) // 4
    # a quarter of the table a chip, donated and written in place
    assert m.alias_size_in_bytes >= shard
    assert shard + cfg.pipeline_groups * (need - shard) < HBM_BYTES
    f0 = state["db"]["MAIN_TABLE"].columns["F0"]
    assert f0.sharding.shard_shape(f0.shape) == (f0.shape[0] // 4, 100)
    out_db = compiled.output_shardings[0]
    assert out_db["MAIN_TABLE"].columns["F0"].is_equivalent_to(
        f0.sharding, 2)


def test_dp4_cell_group_names_what_the_mesh_adds(dp4_cell):
    """On the chip's own HLO: the collectives of `execute_mc` sit under
    `ep.exchange` (an all-reduce the compiler merged may keep no op_name
    — `phase_reduce.hlo_scopes` then reads its consumers'), the exchange
    blocks are cut at the batch's real width (10 accesses: 20,480 lanes
    a block), and the per-shard plan sort is `ep.plan`'s.

    Since PR 41 the exchange counts its owners with compares: nothing
    under `ep.exchange` is a scatter or a `kCustom` fusion (the
    `jnp.bincount` behind the block starts was a scatter-add of 40,960
    lanes into `s32[5]`, 0.38 ms an epoch), and the capacity-defer
    pass sits in a conditional of that scope — two of its three sorts
    are a branch's, whose `op_name` keeps the scope, so a trace still
    charges them to `phase.exchange`."""
    hlo = dp4_cell[3].as_text()
    assert "mc_defer_pass_cnt" in dp4_cell[2]["stats"]
    seen = {}
    for ln in hlo.splitlines():
        m = re.search(r" (all-to-all|all-gather|all-reduce)[a-z\-]*\(", ln)
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', ln)
        seen.setdefault(m.group(1), []).append(ln)
        assert name is None or "ep.exchange" in name.group(1).split("/"), ln
        assert name is not None or m.group(1) == "all-reduce", ln
    assert len(seen["all-to-all"]) == 3 and len(seen["all-gather"]) == 1
    assert "all-reduce" in seen
    assert any("s32[4,1,20480]" in ln for ln in seen["all-to-all"])
    sorts = [re.search(r'op_name="([^"]*)"', ln).group(1).split("/")
             for ln in hlo.splitlines() if re.search(r" sort\(", ln)]
    assert all("ep.exchange" in s for s in sorts)
    inner = [next(p for p in reversed(s) if p.startswith("ep."))
             for s in sorts]
    # (`ep.read`: the read heads' compaction, PR 30)
    assert sorted(inner) == ["ep.exchange"] * 3 + ["ep.plan", "ep.read",
                                                   "ep.write"]
    # every instruction whose innermost scope is the exchange's, fused
    # computations' bodies included, by the computation that holds it
    where, exchange = None, []
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(", ln)
        if head:
            where = head.group(1)
        name = re.search(r'op_name="([^"]*)"', ln)
        scopes = [p for p in name.group(1).split("/")
                  if p.startswith(("ep.", "grp."))] if name else []
        if scopes and scopes[-1] == "ep.exchange":
            exchange.append((where, ln))
    assert not [ln for _, ln in exchange
                if " scatter(" in ln or "kind=kCustom" in ln]
    (cond,) = [ln for _, ln in exchange if " conditional(" in ln]
    branches = re.search(r"branch_computations=\{([^}]*)\}", cond)
    branches = branches.group(1).split(", ")
    assert len(branches) == 2
    # the (owner, ts, txn) and the (txn, over) sort are the pass's: in
    # ONE branch; the five-operand owner sort runs in every epoch
    held = [where for where, ln in exchange if re.search(r" sort\(", ln)]
    assert len(held) == 3
    assert sorted(held.count(b) for b in branches) == [0, 2]


def test_dp4_cell_group_reads_and_writes_each_shard_in_place(dp4_cell):
    """The one-chip pin (`test_full_row_column_is_written_in_place_
    inside_the_epoch_scan`) on the four-chip cell: no chip copies its
    629 MB shard of the column inside an epoch — the two relayouts in
    the entry computation stay, once a group — and a shard's 81,920
    plan lanes reach the row gather 1,280 a call, in a `while` body
    under `ep.read` (PR 47); its winners reach the row write 1,280 a
    call, XLA's scatter as at the parent — the kernel of PR 48 is not
    compiled in below calls of 2,560 lanes (`ops.scatter.
    _MIN_CALL_LANES`: no call of 1,280 was priced or served on a chip),
    though the shard's server counts its groups (0) like every YCSB
    server."""
    cfg, _, state, compiled, _ = dp4_cell
    f0 = state["db"]["MAIN_TABLE"].columns["F0"]
    rows, width = f0.sharding.shard_shape(f0.shape)
    hlo = compiled.as_text()
    copies = _column_copies(hlo, f"u8[{rows},{width}]")
    assert copies and set(copies) == {"entry"}, copies
    from deneva_tpu.ops import mc_pair_cap
    lanes = 4 * mc_pair_cap(cfg.epoch_batch, cfg.req_per_query, 4,
                            cfg.mc_plan_capacity)
    assert lanes == 81_920
    _xlas_row_write_loop(hlo, f"u8[{rows},{width}]", lanes)
    ((got, scopes),) = _looped_row_gathers(hlo, width)
    assert got == -(-lanes // G._CHUNKS)
    assert scopes[scopes.index("ep.read"):][1:3] == ["while", "body"]


# ---- the OCC cell (PR 32): ycsb_fullrow_occ.medium ------------------------

def test_occ_cell_group_validates_without_arenas_or_matmuls(one_chip,
                                                            monkeypatch):
    """The OCC cell's group program on the chip's own HLO: no scatter
    but the row write's two (the four `access_incidence` scatter-adds
    of 10,240 lanes into `bf16[1024 x 8192]` arenas went, 0.45 ms an
    epoch at PR 31; the loop's scatter of 160 lanes a trip stays XLA's,
    below the row write kernel's `_MIN_CALL_LANES`: PR 48), no
    convolution under `ep.validate` (the two bucket matmuls, 0.18 ms) — the U-vs-W conflict matrix is
    `ops.conflict.key_overlap`'s compare — nothing of an arena's size
    anywhere in the program, and it still fits the chip."""
    cfg = _cell_cfg("ycsb_fullrow_occ.medium")
    b, k = cfg.epoch_batch, cfg.conflict_buckets
    assert (cfg.cc_alg, b, k, cfg.conflict_exact) == ("OCC", 1024, 8192,
                                                      True)
    group, state, feed = _group_program(cfg, monkeypatch)
    state, feed = _with_sharding((state, feed), one_chip)
    compiled, secs = _compile(group, state["db"], state["cc_state"],
                              state["stats"], *feed)
    need = _report("occ_cell_group", compiled, secs)
    table = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"]))
    assert table + cfg.pipeline_groups * (need - table) < HBM_BYTES
    hlo = compiled.as_text()
    assert f"[{b},{k}]" not in hlo and f"[{b * k}]" not in hlo
    validate = [ln for ln in hlo.splitlines() if re.search(
        r'op_name="[^"]*/ep\.validate/', ln)]
    assert len(validate) > 100
    assert any(" compare(" in ln for ln in validate)
    assert not [ln for ln in validate if " convolution(" in ln]
    # (a scatter inside a fusion keeps no op_name: by what it writes —
    # the program's only scatters are `ep.write`'s, into the row column)
    f0 = state["db"]["MAIN_TABLE"].columns["F0"]
    scatters = [ln for ln in hlo.splitlines() if " scatter(" in ln]
    assert scatters and all(
        f"= u8[{f0.shape[0]},{f0.shape[1]}]" in ln for ln in scatters)
    _xlas_row_write_loop(hlo, f"u8[{f0.shape[0]},{f0.shape[1]}]",
                         b * cfg.req_per_query)
    # the matrix itself: one [B, B] result of A x A fused compares
    assert re.search(rf"f32\[{b},{b}\]\S* fusion\(", hlo)


# ---- the WAIT_DIE cell (PR 46): ycsb_fullrow_waitdie.medium ----------------

def test_waitdie_cell_group_fits_the_chip_and_sweeps_under_validate(
        one_chip, monkeypatch):
    """The WAIT_DIE cell's group program at its served size, with the
    lock counters its server asks for, on the chip's own HLO: it fits
    beside two groups in flight; the lock table — the [B, B] compare of
    the exact keys, the sweep's matvecs and the age test's masked min —
    carries `ep.validate`; nothing of an arena's size anywhere, and the
    program's only scatters are the row write's (XLA's loop of 160
    lanes a trip: below the kernel's `_MIN_CALL_LANES`, PR 48)."""
    from deneva_tpu.engine.step import init_device_stats
    cfg = _cell_cfg("ycsb_fullrow_waitdie.medium")
    b, k = cfg.epoch_batch, cfg.conflict_buckets
    assert (cfg.cc_alg, cfg.isolation_level, b, cfg.sweep_rounds,
            cfg.defer_rounds_max) == ("WAIT_DIE", "SERIALIZABLE", 1024,
                                      24, 8)
    group, state, feed = _group_program(cfg, monkeypatch)
    state["stats"] = jax.eval_shape(
        lambda: init_device_stats(1, lock_counters=True, row_groups=True))
    assert {"lock_die", "lock_wait", "lock_leftover",
            "write_row_groups"} <= set(state["stats"])
    state, feed = _with_sharding((state, feed), one_chip)
    compiled, secs = _compile(group, state["db"], state["cc_state"],
                              state["stats"], *feed)
    need = _report("waitdie_cell_group", compiled, secs)
    table = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"]))
    assert table + cfg.pipeline_groups * (need - table) < HBM_BYTES
    hlo = compiled.as_text()
    assert f"[{b},{k}]" not in hlo and f"[{b * k}]" not in hlo
    validate = [ln for ln in hlo.splitlines() if re.search(
        r'op_name="[^"]*/ep\.validate/', ln)]
    assert len(validate) > 100
    assert any(" compare(" in ln for ln in validate)
    # the sweep's rounds: a loop of [B, B] x [B] products under the scope
    assert any(re.search(r" (dot|convolution)\(", ln) or " while(" in ln
               for ln in validate)
    f0 = state["db"]["MAIN_TABLE"].columns["F0"]
    scatters = [ln for ln in hlo.splitlines() if " scatter(" in ln]
    assert scatters and all(
        f"= u8[{f0.shape[0]},{f0.shape[1]}]" in ln for ln in scatters)
    _xlas_row_write_loop(hlo, f"u8[{f0.shape[0]},{f0.shape[1]}]",
                         b * cfg.req_per_query)
    # the matrix itself: ONE fusion of A x A compares, handed on twice —
    # as f32 to the sweep's products, as pred to the age test
    assert re.search(rf"= \(f32\[{b},{b}\]\S*, pred\[{b},{b}\]\S*\) "
                     r"fusion\(", hlo)


# ---- the MVCC cell (PR 43): ycsb_fullrow_mvcc.medium ----------------------

def test_mvcc_cell_group_fits_the_chip_and_names_its_ring(one_chip,
                                                         monkeypatch):
    """The MVCC cell's group program at its served size, on the chip's
    own HLO: 6.29M full rows, the ring of ten timestamps a row and the
    watermark tables fit beside two groups in flight; the ring's gather,
    select and push carry `ep.version`; deciding an epoch scatters into
    the `int32[2^20]` watermark tables three times — the reads'
    watermark, the epoch's greatest and least write of a bucket — as the
    parent's did (the writes' watermark is a dense max since PR 43).

    **The ring's storage form (PR 45): `uint8[6,291,520, 40]`**, a row
    its ten int32 timestamps as bytes — 251,660,800 B, nothing padded
    (the chip tiles it `{0,1:T(8,128)(4,1)}`, rows minor, as it does
    TPC-C's narrow string columns).  A lane's history is ONE row gather
    of 40 B slices (10,240 lanes an epoch, no gather of words out of the
    ring), the push the table's own winners-only row write
    (`ops.scatter.scatter_winner_rows`: a loop of 160-lane scatters, or
    one sorted scatter, in the two branches of a conditional), and the
    compiled program holds NO copy and no second buffer of the ring
    (neither goes through PR 48's kernel):
    nothing but parameters, tuple elements and those two scatters has its
    shape.  The forms not taken, compiled here for the same chip
    (PERF.md section 6, PR 45): `int32[6,291,520, 10]` is tiled
    `{0,1:T(8,128)}`, rows minor with the ten words padded to sixteen —
    402,657,280 B, 1.6x, and a "row" is ten strided words again —; the
    flat `int32[62,915,200]` of PRs 22-43 gathered 102,400 scalars and
    its scatter of every lane copied the array each epoch."""
    from deneva_tpu.engine.step import init_device_stats
    cfg = _cell_cfg("ycsb_fullrow_mvcc.medium")
    assert (cfg.cc_alg, cfg.mvcc_his_len, cfg.epoch_batch,
            cfg.watermark_buckets) == ("MVCC", 10, 1024, 1 << 20)
    group, state, feed = _group_program(cfg, monkeypatch)
    state["stats"] = jax.eval_shape(
        lambda: init_device_stats(2, mvcc_counters=True, row_groups=True))
    state, feed = _with_sharding((state, feed), one_chip)
    compiled, secs = _compile(group, state["db"], state["cc_state"],
                              state["stats"], *feed)
    need = _report("mvcc_cell_group", compiled, secs)
    table = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"]))
    ring = state["db"]["MAIN_TABLE.F0.ver"].wts
    assert (ring.shape, ring.dtype) == ((6_291_520, 40), np.uint8) \
        and table > 6_500_000_000
    assert table + cfg.pipeline_groups * (need - table) < HBM_BYTES
    hlo = compiled.as_text()
    # as the chip lays it out: its bytes, not a padded tile's
    shape = _hlo_shape(ring)
    (layout,) = set(re.findall(re.escape(shape) + r"(\{[^}]*\})", hlo))
    minor = int(re.match(r"\{(\d),", layout).group(1))
    tile = [int(x) for x in re.search(r"T\((\d+),(\d+)\)", layout).groups()]
    pad = lambda n, t: -(-n // t) * t  # noqa: E731
    laid = pad(ring.shape[minor], tile[1]) * pad(
        ring.shape[1 - minor], tile[0]) * ring.dtype.itemsize
    assert ring.size == 251_660_800 and laid <= 1.1 * 251_660_800
    named = [ln for ln in hlo.splitlines() if re.search(
        r'op_name="[^"]*/ep\.version/', ln)]
    assert len(named) > 50
    # one row gather a lane, of the whole 40 B, under the scope; no words
    row_gathers = [ln for ln in hlo.splitlines()
                   if " gather(" in ln and "slice_sizes={1,40}" in ln]
    assert len(row_gathers) == 1 and "/ep.version/" in row_gathers[0] \
        and "u8[1024,10,40]" in row_gathers[0]
    lanes = cfg.epoch_batch * cfg.req_per_query
    assert f"s32[{lanes * cfg.mvcc_his_len}]" not in hlo
    # no copy, no second buffer: what has the ring's shape is the state
    # passed along and the two forms of the in-place row write
    makers = set(re.findall(
        r"= " + re.escape(shape) + r"\{[^}]*\} ([\w\-]+)\(", hlo))
    assert makers == {"parameter", "get-tuple-element", "fusion", "scatter"}
    assert _column_copies(hlo, shape) == []
    scatters = [ln for ln in hlo.splitlines() if " scatter(" in ln]
    by_shape = [re.search(r"= (\w+\[[\d,]*\])", ln).group(1)
                for ln in scatters]
    assert set(by_shape) == {"s32[1048576]", shape, "u8[6291520,100]"}
    assert (by_shape.count("s32[1048576]"), by_shape.count(shape)) == (3, 2)
    assert _scatter_lanes(hlo, shape) == [lanes // 64, lanes]
    # neither column's winners go through the row write's kernel
    # (PR 48): calls of 160 lanes are below its `_MIN_CALL_LANES` (F0's
    # 2,580 winners cost it 0.30 ms an epoch for XLA's 0.24 when forced
    # through it, and the cell served 3% less: my chip runs, PR 48),
    # and the ring could not
    # at any length — the kernel's operand is row-major, the chip lays
    # 40 B rows rows-minor, and forced through the kernel this program
    # relayouts the ring twice inside every epoch (two `inner` copies,
    # temporaries 0.81 -> 2.44 GB: my compile for v5e, PR 48)
    _xlas_row_write_loop(hlo, "u8[6291520,100]", lanes)
    assert _row_write_kernels(hlo, shape) == []
    from deneva_tpu.ops.scatter import _by_group
    assert _by_group((6_291_520, 100), np.uint8, 2560) \
        and not _by_group((6_291_520, 100), np.uint8, lanes // 64) \
        and not _by_group(ring.shape, ring.dtype, 2560)


# ---- the TPC-C cell (PR 36): tpcc_fullschema_tpubatch.mixed --------------

CELL_TPCC = "tpcc_fullschema_tpubatch.mixed"


def test_tpcc_full_schema_deployment_fits_one_v5e(one_chip, monkeypatch):
    """128 warehouses at the full schema's row widths, as the cell's timed
    launch builds them: the nine tables are 8.6 GB of rows and the chip
    holds them in under 9 GB (narrow `uint8[rows, size]` columns are
    tiled with the ROWS minor: a width that is a multiple of 8 pads
    nothing); the group program updates them in place with tens of MB
    of temporaries and copies none of the wide columns; the loader —
    one program for the numbers, one a string column in place — never
    needs more than the tables and half a GB.

    Since PR 39 a level pass whose level fits 32 or 128 transactions
    runs at that width (`engine/epoch.level_widths`): two more executor
    bodies beside the whole-batch one, under two-way `lax.cond`s with
    the narrowest innermost.  All three update the 8.6 GB in place,
    append through windows and scatter into STOCK at their own lane
    counts.  (Under a `lax.switch`, and in a nest that leans the other
    way, the chip's compiler copied `OL_DIST_INFO`, `H_DATA` and a
    STOCK column whole inside a middle branch: my compiles for v5e,
    PR 39.)"""
    from deneva_tpu.engine.epoch import level_widths
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.workloads import get_workload
    from deneva_tpu.workloads.tpcc import S_DIST, _string_filler
    cfg = _cell_cfg(CELL_TPCC)
    assert (cfg.num_wh, cfg.sim_full_row, cfg.epoch_batch) == (128, True,
                                                               1024)
    group, state, feed = _group_program(cfg, monkeypatch)
    # the server of a chained backend counts its level passes, and one
    # whose workload has ring tables how their appends were written
    state["stats"] = jax.eval_shape(
        lambda: init_device_stats(2, level_passes=True, append_lanes=True))
    state, feed = _with_sharding((state, feed), one_chip)
    table = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"]))
    assert 8.5e9 < table < 8.7e9
    compiled, secs = _compile(group, state["db"], state["cc_state"],
                              state["stats"], *feed)
    need = _report("tpcc_fullschema_128wh", compiled, secs)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= table           # updated in place
    assert m.output_size_in_bytes < 1.03 * table    # rows minor: no padding
    assert m.temp_size_in_bytes < 256e6
    assert table + cfg.pipeline_groups * (need - table) < 0.6 * HBM_BYTES
    hlo = compiled.as_text()
    for name, col in (("STOCK", S_DIST), ("ORDER-LINE", "OL_DIST_INFO"),
                      ("CUSTOMER", "C_DATA")):
        c = state["db"][name].columns[col]
        assert not _column_copies(hlo, f"u8[{c.shape[0]},{c.shape[1]}]")
    # the four rings are appended to through windows (PR 37): every ring
    # column by `dynamic-update-slice` (one window at the cursor, one at
    # row 0 for what wraps), none by a scatter, none copied
    rings = ("HISTORY", "ORDER", "NEW-ORDER", "ORDER-LINE")
    shapes = {_hlo_shape(c) for t in rings
              for c in state["db"][t].columns.values()}
    assert shapes == {"s32[31457344]", "f32[31457344]", "u8[31457344,24]",
                      "s32[2097216]", "f32[2097216]", "u8[2097216,24]"}
    n_cols = sum(len(state["db"][t].columns) for t in rings)
    widths = level_widths(cfg.epoch_batch)
    assert widths == (32, 128, cfg.epoch_batch)
    windows = 0
    for shape in shapes:
        assert not _column_copies(hlo, shape), shape
        assert not re.search(
            r"= " + re.escape(shape) + r"\S* scatter\(", hlo), shape
        windows += len(re.findall(
            r"= " + re.escape(shape) + r"\S* dynamic-update-slice\(", hlo))
    assert windows == 2 * n_cols * len(widths) == 58 * len(widths)
    # no column of any table is copied whole by a plain copy (the
    # parent's program had none either), and STOCK's four scatters are
    # there once a width, at that width's lanes
    for tab in state["db"].values():
        for c in tab.columns.values():
            if c.size * c.dtype.itemsize > 1 << 20:
                assert not _column_copies(hlo, _hlo_shape(c)), _hlo_shape(c)
    ipt = cfg.max_items_per_txn
    assert _scatter_lanes(hlo, "s32[12800064]") == sorted(
        4 * [w * ipt for w in widths])
    # (the compiler's own asynchronous copies of a STOCK column, parts
    # of its scatters: two in the parent's one body, no more now)
    assert len(re.findall(r"= \(s32\[12800064\]\S*, s32\[12800064\]\S*, "
                          r"\S+ copy-start\(", hlo)) <= 2
    # the loader: the numbers' program, then the widest string column
    wl = get_workload(cfg)
    built, secs = _compile(jax.jit(wl._build_db, out_shardings=one_chip))
    assert _report("tpcc_build_db", built, secs) < 1.03 * table
    cells = state["db"]["STOCK"].columns[S_DIST]
    fill, secs = _compile(
        _string_filler(True), cells,
        jax.ShapeDtypeStruct((), np.uint32, sharding=one_chip),
        wl.n_stock_loc, 10)
    m = fill.memory_analysis()
    _report("tpcc_fill_s_dist", fill, secs)
    assert m.alias_size_in_bytes >= cells.size and \
        m.temp_size_in_bytes < 1e9


# ---- the PPS cell (PR 38): pps_fullrow_tpubatch.lookup_order_update ------

CELL_PPS = "pps_fullrow_tpubatch.lookup_order_update"


def test_pps_group_program_compiles_for_v5e(one_chip, monkeypatch):
    """The PPS cell's group program at its timed launch's shapes (epochs
    of 1,024 lanes over 21 accesses, C=8, the five tables at the
    schema's row widths: 1.46 MB): the chip's compiler accepts it; the
    only scatters are the part adds and the mapping write (both into a
    `s32[10048]` column: nothing scatters into a table's string bytes
    or copies them inside an epoch); a pass gathers whole part rows in ONE row gather of
    `u8[11264, 100]`; and the stale test is one more `[1024, 1024]`
    compare beside `validate_calvin`'s."""
    from deneva_tpu.engine.step import init_device_stats
    from deneva_tpu.workloads.pps import FIELDS
    cfg = _cell_cfg(CELL_PPS)
    assert (cfg.pps_parts_cnt, cfg.sim_full_row, cfg.epoch_batch,
            cfg.max_accesses) == (10000, True, 1024, 21)
    group, state, feed = _group_program(cfg, monkeypatch)
    # the server of a chained backend counts its level passes, and one
    # whose workload marks reconnaissance the lanes deferred on a stale one
    state["stats"] = jax.eval_shape(
        lambda: init_device_stats(8, level_passes=True, recon_defers=True))
    state, feed = _with_sharding((state, feed), one_chip)
    table = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(state["db"]))
    assert 1.4e6 < table < 1.5e6
    compiled, secs = _compile(group, state["db"], state["cc_state"],
                              state["stats"], *feed)
    need = _report("pps_fullrow", compiled, secs)
    assert need < 64e6
    hlo = compiled.as_text()
    amount = _hlo_shape(state["db"]["PARTS"].columns["PART_AMOUNT"])
    assert amount == _hlo_shape(state["db"]["USES"].columns["PART_KEY"]) \
        == "s32[10048]"
    scatters = [ln for ln in hlo.splitlines() if " scatter(" in ln]
    assert scatters and all(f"= {amount}" in ln for ln in scatters)
    strings = _hlo_shape(state["db"]["PARTS"].columns[FIELDS])
    # (a relayout copy of the 1 MB of strings once a GROUP is the entry
    # computation's, as the YCSB cells have it; none inside an epoch)
    assert strings == "u8[10048,100]" \
        and "inner" not in _column_copies(hlo, strings)
    # (one such gather a width of `engine/epoch.level_widths`: PR 39)
    from deneva_tpu.engine.epoch import level_widths
    b, per = cfg.epoch_batch, cfg.pps_parts_per
    assert _row_gathers(hlo, 100) == {w * (per + 1)
                                      for w in level_widths(b)}
    assert len(re.findall(rf"pred\[{b},{b}\]\S* fusion\(", hlo)) >= 2


# ---- the three YCSB cells and `run_levels` (PR 39) ------------------------

@pytest.mark.parametrize("cell", [
    "ycsb_fullrow_tpubatch.hot", "ycsb_fullrow_occ.medium", CELL_DP4])
def test_the_ycsb_cells_programs_never_reach_run_levels(cell, monkeypatch):
    """The forwarding executor returns from `epoch_core` before the
    chained levels and OCC is not chained: with `run_levels` made
    unreachable the three cells' group programs still trace, and their
    stats carry neither pass counter — what PR 39 changed is not in
    them (their stripped v5e HLO is the parent's line for line: PERF.md
    section 6)."""
    import contextlib
    from deneva_tpu.engine import epoch
    from deneva_tpu.parallel import mesh as M

    def unreachable(*a, **kw):
        raise AssertionError("run_levels reached")
    monkeypatch.setattr(epoch, "run_levels", unreachable)
    cfg = _cell_cfg(cell)
    group, state, feed = _group_program(cfg, monkeypatch)
    assert not {"level_pass_cnt", "narrow_pass_cnt"} & set(state["stats"])
    with (M.use_mesh(M.make_mesh(cfg.device_parts))
          if cfg.device_parts > 1 else contextlib.nullcontext()):
        out = jax.eval_shape(group, state["db"], state["cc_state"],
                             state["stats"], *feed)
    assert jax.tree.structure(out[2]) == jax.tree.structure(state["stats"])
