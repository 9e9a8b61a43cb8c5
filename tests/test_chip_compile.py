"""Ask the v5e's compiler, without a chip, whether the training path lowers.

Interpret mode cannot see what Mosaic and XLA:TPU refuse (an unimplemented
primitive, a misaligned block, a program that does not fit 16 GB); these
ahead-of-time compiles for a DESCRIBED chip can, at HIGGS's widths: 28
features -> a uint8[32, N] plane, max_bin=255, 3 gh channels, the default
wave width 21, N = 2^20. Nothing runs, so they say nothing about results or
times — `python chip_smoke.py` on a chip does.

The topology is described inside a module-scoped fixture of THIS file and
nowhere else (only one process may hold the TPU library; see the
on-chip-measurement guide), every compile happens in the test's own process,
and the persistent compilation cache is off around them: an executable
compiled for an absent chip cannot be read back.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.ops.compact_pallas import (COMPACT_TILE, _pallas_compact_call,
                                             max_pairs_bound)
from lightgbm_tpu.ops.hist_pallas import (DEFAULT_TILE_ROWS, pallas_histogram,
                                          pallas_histogram_slots_ragged)
from lightgbm_tpu.ops.predict import (PackedEnsemble, _predict_raw_dense,
                                      _predict_raw_fused)
from lightgbm_tpu.treelearner import device as device_mod

N = 1 << 20
FEATURES, GROUPS_PADDED, BINS, WAVE_K = 28, 32, 255, 21
HBM_BYTES = int(15.75 * 2 ** 30)  # what the v5e compiler admits a program
# The whole-tree program's temp at N = 2^20 read 307,058,688 B (float32) and
# 307,123,200 B (quantized) once every per-row carry was [k, N] (AOT, PR 29);
# with rows on the sublanes one [N, k] float32 carry alone was 0.54 GB and
# the program 4.47 GB (PR 22). 15 % above the reading.
TREE_TEMP_CEILING = 353_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    """shape, dtype -> an abstract array placed on one described chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# What the four training cells' whole-tree programs ask (AOT, PR 35; the
# parent's, PR 34, in brackets): temp bytes, then the scoped VMEM of the wave
# histogram call / the root histogram call / the compaction call.
#   2^20 x 28 float32      232,384,512 (231,934,976)   65,536 (327,680) / 65,536 (65,536) / 98,304
#   2^20 x 28 quantized    232,512,512 (231,998,464)   65,536 (323,584) / 65,536 (65,536) / 98,304
#   10,502,144 x 28 on four chips, a chip
#                          710,413,824 (710,519,296)  229,376 (716,800) / 229,376 (225,280) / 131,072
#   10,500,000 x 28 quantized
#                        2,160,010,240 (2,159,524,864) 98,304 (356,352) / 98,304 (98,304) / 131,072
#   2,270,296 x 136 float32
#                        1,035,742,720 (1,035,323,392) 221,184 (4,923,392) / 221,184 (217,088) / 598,016
# The (tile, slot) pair kernel's operand and output block are one slot
# high, so the wave call asks what the root call asks.
def _tree_kernels(compiled, capsys, what: str) -> list:
    """The whole-tree program's histogram calls, after checking that it
    holds exactly two (root and waves: one kernel, `n_slots` 1 and WAVE_K)
    and one compaction call; prints what the program asks of the chip
    (PERF.md keeps the readings beside the parent's)."""
    kernels = [ln for ln in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    hist = [ln for ln in kernels if "pallas_histogram_slots_ragged" in ln]
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nAOT {what}: temp {mem.temp_size_in_bytes} arguments "
              f"{mem.argument_size_in_bytes} output "
              f"{mem.output_size_in_bytes}; kernels' VMEM "
              f"{_mosaic_vmem_bytes(compiled)}")
    assert len(hist) == 2 and len(kernels) - len(hist) == 1
    assert sum("_pallas_compact_call" in ln for ln in kernels) == 1
    return hist


def _compact_dst_operand(compiled) -> str:
    """The HLO instruction that makes the compaction kernel's last operand,
    dst [1, N]."""
    text = compiled.as_text()
    call = next(ln for ln in text.splitlines()
                if "_pallas_compact_call" in ln and " custom-call(" in ln)
    name = call.split(" custom-call(")[1].split(")")[0].split(", ")[-1]
    return next(ln for ln in text.splitlines()
                if ln.lstrip().startswith(name + " = "))


def _rows_on_sublanes(compiled, n_rows: int) -> list:
    """Arrays of the compiled program laid out [n_rows, k<100] with the rows
    on the sublanes: k pads to 128 lanes, so each is n_rows * 512 bytes and
    every operation on it runs at 8 useful values a vector register. One
    such operand of a kernel drags the layout through the wave's glue
    (PERF.md, PR 29: 2.28 s of a 4.24 s tree at N = 2^22)."""
    return sorted(set(re.findall(
        r"\w+\[%d,\d{1,2}\]\{1,0:T\(8,128\)[^}]*\}" % n_rows,
        compiled.as_text())))


@pytest.mark.parametrize("quantized", [False, True])
def test_dense_histogram_kernel_compiles(on_chip, quantized):
    gh = jnp.int8 if quantized else jnp.float32
    compiled = pallas_histogram.lower(
        on_chip((FEATURES, N), jnp.uint8), on_chip((N, 3), gh),
        num_bins=BINS, quantized=quantized, interpret=False).compile()
    assert _mosaic_calls(compiled) == 1


def _mosaic_matmuls(compiled) -> list:
    """(lhs, rhs, result) vector types of every matmul in the compiled
    program's Mosaic kernel bodies, decoded from the custom calls' MLIR
    bytecode."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    found = []
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    for body in re.findall(r'"custom_call_config":\{"body":"([^"]+)"',
                           compiled.as_text()):
        with ctx:
            text = str(ir.Module.parse(base64.b64decode(body)))
        found += re.findall(
            r'tpu\.matmul"?\(.*:\s*\(vector<([^>]+)>, vector<([^>]+)>, '
            r'vector<([^>]+)>\)', text)
    return found


def _mosaic_vmem_bytes(compiled) -> dict:
    """What each Mosaic kernel of the compiled program asks of VMEM: the
    scoped allocation the TPU compiler records on its custom call."""
    found = {}
    for ln in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in ln:
            continue
        size = re.search(r'"used_scoped_memory_configs":\[\{"memory_space":'
                         r'"1","offset":"\d+","size":"(\d+)"\}', ln)
        found[ln.split(" = ")[0].strip().lstrip("%")] = (
            int(size.group(1)) if size else None)
    return found


@pytest.mark.parametrize("policy", ["f32", "bf16", "int"])
@pytest.mark.parametrize("groups", [FEATURES, 136])
@pytest.mark.parametrize("n_slots", [1, WAVE_K])
def test_ragged_histogram_kernel_compiles(on_chip, capsys, n_slots, groups,
                                          policy):
    """1 slot is the root pass, WAVE_K the wave's smaller children; 28
    groups are HIGGS's plane (one 32-group block, four of them padding), 136
    MSLR-WEB30K's (five blocks, the last with 8 real groups). f32 is the
    path every float benchmark cell runs: three bfloat16 limbs of the
    gradients against a bfloat16 one-hot, so NO policy leaves a float32 x
    float32 matmul (six MXU passes at Precision.HIGHEST) in the body. The
    operand is ONE slot high whatever n_slots is (a pair of the grid
    contracts its own slot's rows: 16 packed rows a limb), the result a
    block a slot, and the groups that pad the plane get no contraction."""
    pairs = N // DEFAULT_TILE_ROWS + 2 * n_slots
    padded = -(-groups // GROUPS_PADDED) * GROUPS_PADDED
    compiled = pallas_histogram_slots_ragged.lower(
        on_chip((padded, N), jnp.uint8), on_chip((3, N), jnp.float32),
        on_chip((N,), jnp.int32), on_chip((pairs,), jnp.int32),
        on_chip((pairs,), jnp.int32), on_chip((1,), jnp.int32),
        num_bins=BINS, n_slots=n_slots, quantized=policy == "int",
        f32=policy == "f32", n_groups=groups, interpret=False).compile()
    assert _mosaic_calls(compiled) == 1
    call = next(ln for ln in compiled.as_text().splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln)
    acc = "s32" if policy == "int" else "f32"
    assert f"= {acc}[{n_slots},{padded},3,256]" in call, call[:200]
    matmuls = _mosaic_matmuls(compiled)
    # a whole block's groups, and the last block's real ones where it is
    # not whole (its own branch of the body)
    last = groups - (padded // GROUPS_PADDED - 1) * GROUPS_PADDED
    assert len(matmuls) == (GROUPS_PADDED if padded > GROUPS_PADDED
                            else last)
    rows = (3 if policy == "f32" else 1) * 16
    for lhs, rhs, res in matmuls:
        assert lhs == f"{rows}x{DEFAULT_TILE_ROWS}xbf16"
        assert rhs == f"256x{DEFAULT_TILE_ROWS}xbf16"  # one-hot [Bp, TN]
        assert res == f"{rows}x256xf32"
    with capsys.disabled():
        print(f"\nAOT histogram kernel n_slots={n_slots} groups={groups} "
              f"{policy}: VMEM {_mosaic_vmem_bytes(compiled)}")


@pytest.mark.parametrize("plane", [jnp.uint8, jnp.int32])
def test_compaction_kernel_compiles(on_chip, plane):
    """uint8 is HIGGS's plane; int32 (two limbs) is what a dataset whose
    EFB bundles pass 256 bins a group gets with the same default settings."""
    pairs = max_pairs_bound(N // COMPACT_TILE, 2 * WAVE_K)
    compiled = _pallas_compact_call.lower(
        on_chip((GROUPS_PADDED, N), plane), on_chip((8, N), jnp.float32),
        on_chip((N,), jnp.int32), on_chip((pairs,), jnp.int32),
        on_chip((pairs,), jnp.int32), on_chip((pairs,), jnp.int32),
        on_chip((1,), jnp.int32), tile=COMPACT_TILE,
        interpret=False).compile()
    assert _mosaic_calls(compiled) == 1


def _packed_500x255(on_chip) -> PackedEnsemble:
    T, L = 500, 255

    def node(dtype):
        return on_chip((T, L - 1), dtype)

    return PackedEnsemble(
        split_feature=node(jnp.int32), threshold=node(jnp.float32),
        decision_type=node(jnp.int32), left_child=node(jnp.int32),
        right_child=node(jnp.int32), leaf_value=on_chip((T, L), jnp.float32),
        cat_words=on_chip((1,), jnp.uint32), cat_offset=node(jnp.int32),
        cat_n_words=node(jnp.int32), num_leaves=on_chip((T,), jnp.int32),
        max_depth=24, num_trees=T)


def test_predict_program_fits_at_the_streaming_chunk(on_chip):
    """Booster.predict streams 2^18-row chunks; the fused XLA traversal of a
    500-tree x 255-leaf ensemble must fit the chip at that size (at 2^20
    rows in one shot the compiler refuses it: 20.4 GiB)."""
    compiled = _predict_raw_fused.lower(
        _packed_500x255(on_chip), on_chip((1 << 18, FEATURES), jnp.float32),
        num_tree_per_iteration=1).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_dense_predict_program_fits_with_room(on_chip):
    """The gather-free program of the same forest at the streaming chunk:
    rows and trees are blocked inside it, so its temp stays far under the
    chip however many rows a call brings, and no node table is gathered
    (the one gather left is the row gather of X^T by feature id)."""
    packed = dataclasses.replace(
        _packed_500x255(on_chip), dense=True,
        path=on_chip((500, 256, 256), jnp.int8),
        path_depth=on_chip((500, 256), jnp.float32))
    compiled = _predict_raw_dense.lower(
        packed, on_chip((1 << 18, FEATURES), jnp.float32),
        num_tree_per_iteration=1).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 << 30
    text = compiled.as_text()
    assert text.count(" gather(") == 1
    assert "convolution(" in text


@pytest.mark.slow
@pytest.mark.parametrize("quantized", [False, True])
def test_whole_tree_program_compiles_and_fits(on_chip, monkeypatch, capsys,
                                              quantized):
    """grow_tree_on_device whole, ~45 s a compile. The learner asks
    on_tpu() — the CPU, in this process — so the test answers for it."""
    monkeypatch.setattr(device_mod, "on_tpu", lambda: True)
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((4096, FEATURES), dtype=np.float32)
    cfg = Config({"objective": "binary", "num_leaves": 255, "max_bin": BINS,
                  "min_data_in_leaf": 100, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(np.float64),
                                 config=cfg)
    learner = device_mod.DeviceTreeLearner(cfg, ds)

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype),
                                      tree)

    compiled = device_mod.grow_tree_on_device.lower(
        on_chip((FEATURES, N), jnp.uint8),
        on_chip((N, 3), jnp.int8 if quantized else jnp.float32),
        on_chip((N,), jnp.int32), abstract(learner.meta),
        abstract(learner.tables), abstract(learner.params_dev),
        on_chip((FEATURES,), jnp.bool_), num_leaves=255,
        num_bins=learner.group_bin_padded, max_depth=cfg.max_depth,
        quantized=quantized,
        scale_vec=on_chip((3,), jnp.float32) if quantized else None,
        batch=WAVE_K, bagged=False).compile()
    # root histogram, wave histogram, wave compaction
    _tree_kernels(compiled, capsys,
                  f"tree at 2^20 x 28, quantized={quantized}")
    assert _rows_on_sublanes(compiled, N) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < TREE_TEMP_CEILING


HIGGS_ROWS = 10_500_000


@pytest.mark.slow
def test_sharded_whole_tree_program_fits_four_chips_at_higgs_full(
        topo, monkeypatch, capsys):
    """`tree_learner=data, num_machines=4` at Higgs's published 10,500,000
    rows (the benchmark's `higgs_full.train_4chip`): the sharded whole-tree
    program, 2,625,536 rows a shard, compiled for the four described chips
    (~55 s). A shard's has to fit with room for what chip 0 holds besides
    (scores, labels, gradients: ~50 bytes a row of the whole table). Read
    by PR 29: 0.83 GB temp + 0.14 GB arguments a chip (11.03 GB temp at PR
    28, with the per-row carries [N, k]; one chip's program, refused then
    at 35.43 GB, now asks 3.00 GB for the same 10,500,000 rows)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lightgbm_tpu.parallel.learners import DeviceDataParallelTreeLearner
    from lightgbm_tpu.parallel.mesh import padded_row_count

    monkeypatch.setattr(device_mod, "on_tpu", lambda: True)
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")
    rng = np.random.default_rng(0)
    X = rng.standard_normal((8192, FEATURES), dtype=np.float32)
    cfg = Config({"objective": "binary", "num_leaves": 255, "max_bin": BINS,
                  "min_sum_hessian_in_leaf": 100, "tree_learner": "data",
                  "num_machines": 4, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(np.float64),
                                 config=cfg)
    learner = DeviceDataParallelTreeLearner(cfg, ds)  # on four CPU devices
    assert learner.D == 4
    n_pad = padded_row_count(HIGGS_ROWS, 4, learner._row_unit)
    assert n_pad == 10_502_144
    mesh = Mesh(np.array(topo.devices), ("data",))

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def like(tree, spec):
        return jax.tree_util.tree_map(
            lambda a: on(spec, a.shape, a.dtype), tree)

    grow = device_mod.make_sharded_grow_fn(
        mesh, num_leaves=255, num_bins=learner.group_bin_padded,
        max_depth=cfg.max_depth, quantized=False, batch=learner.wave,
        bagged=False, narrow=False)
    compiled = grow.lower(
        on(P(None, "data"), (FEATURES, n_pad), jnp.uint8),
        on(P("data"), (n_pad, 3), jnp.float32),
        on(P("data"), (n_pad,), jnp.int32),
        like(learner._gidx_arg, P()), like(learner._vslot_arg, P()),
        like(learner._scan_meta_arg, P("data")),
        like(learner._tables_rep, P()), like(learner._params_rep, P()),
        on(P("data"), (learner.f_pad,), jnp.bool_),
        on(P(), (3,), jnp.float32)).compile()
    _tree_kernels(compiled, capsys, f"sharded tree at {n_pad} x 28, a chip")
    assert _rows_on_sublanes(compiled, n_pad // 4) == []
    text = compiled.as_text()
    assert " all-reduce(" in text and " all-gather(" in text
    mem = compiled.memory_analysis()  # bytes on each device
    whole_table_on_chip_0 = 50 * HIGGS_ROWS
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + whole_table_on_chip_0) < HBM_BYTES


@pytest.mark.slow
def test_resident_row_programs_hold_no_cross_chip_collective_at_higgs_full(
        topo):
    """What runs between two sharded trees since PR 37, at the cell's
    10,502,144 rows on the four described chips: the gradients, the pack,
    the initial leaf ids and both score updates over operands in the
    learner's row layout compile to programs without one collective (each
    chip its own 2,625,536 rows), and the pack and the leaf ids come out
    in the shardings the tree program above is lowered with; only the
    N-row view, which no tree reads, gathers."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lightgbm_tpu.models import gbdt as gbdt_mod
    from lightgbm_tpu.models.resident import row_programs
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.parallel import learners as learners_mod

    n_pad = 10_502_144
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    layout = learners_mod.RowLayout(HIGGS_ROWS, n_pad, rows)
    cfg = Config({"objective": "binary", "verbosity": -1})
    programs = row_programs(layout, create_objective("binary", cfg))

    def vec(dtype):
        return jax.ShapeDtypeStruct((n_pad,), dtype, sharding=rows)

    def rep(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, P()))

    f32, i32 = vec(jnp.float32), vec(jnp.int32)
    compiled = {
        "gradients": programs.gradients.lower(
            f32, {"_sign": f32, "_lw": f32}).compile(),
        "pack": programs.pack.lower(f32, f32).compile(),
        "leaf ids": learners_mod._root_leaf_ids.lower(
            HIGGS_ROWS, n_pad, rows).compile(),
        "update": gbdt_mod._add_leaf_values_to_score.lower(
            f32, i32, rep((255,))).compile(),
        "update from the log": gbdt_mod._apply_split_log_to_score.lower(
            f32, rep((254, device_mod.STORE)), i32, rep(()),
            num_leaves=255).compile(),
    }
    def collectives(program) -> list:
        text = program.as_text()
        return [op for op in ("all-gather", "all-to-all", "all-reduce",
                              "collective-permute", "reduce-scatter")
                if f" {op}(" in text or f" {op}-start(" in text]

    for name, program in compiled.items():
        assert collectives(program) == [], name
    gh = NamedSharding(mesh, P("data"))  # as the tree program takes [n, 3]
    assert compiled["pack"].output_shardings.is_equivalent_to(gh, 2)
    assert compiled["leaf ids"].output_shardings.is_equivalent_to(rows, 1)
    for name in ("update", "update from the log"):
        assert compiled[name].output_shardings.is_equivalent_to(rows, 1)
    for out in compiled["gradients"].output_shardings:
        assert out.is_equivalent_to(rows, 1)
    assert collectives(programs.cut.lower(f32).compile()) != []


@pytest.mark.slow
def test_quantized_whole_tree_program_fits_one_chip_at_higgs_full(
        on_chip, monkeypatch, capsys):
    """`use_quantized_grad` at Higgs's published 10,500,000 rows on ONE
    described v5e (the benchmark's `higgs_binary_quant.train`): the
    quantized whole-tree program holds both Mosaic kernels, their histogram
    output in int32, no per-row operand on the sublanes, and fits; so does
    the per-tree quantization step beside it. Read by PR 32 (PERF.md): the
    tree program 2,995,152,896 B temp + 420,052,992 B arguments (the float
    one's temp to within a tile), the quantization step 42,339,328 B temp +
    168,002,048 B arguments + 42,001,920 B output."""
    from lightgbm_tpu.ops.quantize import quantize_pack

    monkeypatch.setattr(device_mod, "on_tpu", lambda: True)
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((4096, FEATURES), dtype=np.float32)
    cfg = Config({"objective": "binary", "num_leaves": 255, "max_bin": BINS,
                  "min_sum_hessian_in_leaf": 100, "use_quantized_grad": True,
                  "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(np.float64),
                                 config=cfg)
    learner = device_mod.DeviceTreeLearner(cfg, ds)
    assert learner.quantized and learner.hist_operand == "int"

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype),
                                      tree)

    n = HIGGS_ROWS
    compiled = device_mod.grow_tree_on_device.lower(
        on_chip((FEATURES, n), jnp.uint8), on_chip((n, 3), jnp.int8),
        on_chip((n,), jnp.int32), abstract(learner.meta),
        abstract(learner.tables), abstract(learner.params_dev),
        on_chip((FEATURES,), jnp.bool_), num_leaves=255,
        num_bins=learner.group_bin_padded, max_depth=cfg.max_depth,
        quantized=True, scale_vec=on_chip((3,), jnp.float32),
        batch=WAVE_K, bagged=False).compile()
    hist = _tree_kernels(compiled, capsys, f"quantized tree at {n} x 28")
    # the kernel's result, left of the `=`: a (1, 32, 3, 256) block a slot
    assert sorted(re.search(r"= s32\[(\d+),32,3,256\]", ln).group(1)
                  for ln in hist) == ["1", str(WAVE_K)]
    n_pad = -(-n // 1024) * 1024
    assert _rows_on_sublanes(compiled, n) == []
    assert _rows_on_sublanes(compiled, n_pad) == []
    # the destinations are made before the pair tables, so the compiler
    # prefetches them into fast memory under the tables' sort; made last
    # they stay in HBM and the kernel reads 11 % slower (PERF.md, PR 33)
    assert re.search(r"s32\[1,\d+\]\{1,0:T\(1,128\)S\(1\)\}",
                     _compact_dst_operand(compiled))
    mem = compiled.memory_analysis()
    tree_bytes = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert tree_bytes < HBM_BYTES

    step = quantize_pack.lower(
        on_chip((n + 1, 3), jnp.float32), on_chip((2,), jnp.uint32),
        num_bins=4, stochastic=True).compile()
    smem = step.memory_analysis()
    step_bytes = (smem.temp_size_in_bytes + smem.argument_size_in_bytes
                  + smem.output_size_in_bytes)
    assert step_bytes < HBM_BYTES
    with capsys.disabled():
        print(f"AOT quantize step at {n} rows: temp "
              f"{smem.temp_size_in_bytes} arguments "
              f"{smem.argument_size_in_bytes} output "
              f"{smem.output_size_in_bytes}")


MSLR_ROWS, MSLR_QUERIES, MSLR_FEATURES = 2_270_296, 18_919, 136
# The whole-tree program at uint8[136, 2,270,296] read 1,035,323,392 B of
# temp + 354,362,880 B of arguments (AOT, ISSUE 34 and PR 34): 15 % above.
MSLR_TREE_TEMP_CEILING = 1_190_000_000


@pytest.mark.slow
def test_whole_tree_and_gradient_programs_fit_one_chip_at_mslr(
        on_chip, monkeypatch, capsys):
    """The benchmark's `mslr_lambdarank.train` on ONE described v5e: the
    whole-tree program at MSLR-WEB30K Fold 1's 2,270,296 rows x 136 features
    (a plane of 136 groups: five 32-group blocks in the histogram kernel's
    grid, the last with 8 real groups; float32 histogram operands as the
    configuration's `env` states) holds three Mosaic calls, no per-row
    operand on the sublanes, and its temp stays under the ceiling; the ONE
    lambdarank gradient program at the cell's query layout (18,919 queries of
    1..1,251 documents, nine length buckets) fits beside it."""
    import importlib.util
    import pathlib

    from lightgbm_tpu.io.metadata import Metadata
    from lightgbm_tpu.objectives import create_objective

    monkeypatch.setattr(device_mod, "on_tpu", lambda: True)
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")
    rng = np.random.default_rng(0)
    X = rng.standard_normal((4096, MSLR_FEATURES), dtype=np.float32)
    cfg = Config({"objective": "lambdarank", "num_leaves": 255,
                  "max_bin": BINS, "min_data_in_leaf": 0,
                  "min_sum_hessian_in_leaf": 100, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(np.float64),
                                 config=cfg)
    learner = device_mod.DeviceTreeLearner(cfg, ds)
    assert learner.bins_dev.shape[0] == MSLR_FEATURES

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype),
                                      tree)

    n = MSLR_ROWS
    compiled = device_mod.grow_tree_on_device.lower(
        on_chip((MSLR_FEATURES, n), jnp.uint8), on_chip((n, 3), jnp.float32),
        on_chip((n,), jnp.int32), abstract(learner.meta),
        abstract(learner.tables), abstract(learner.params_dev),
        on_chip((MSLR_FEATURES,), jnp.bool_), num_leaves=255,
        num_bins=learner.group_bin_padded, max_depth=cfg.max_depth,
        quantized=False, scale_vec=learner._scale_vec, batch=WAVE_K,
        bagged=False).compile()
    hist = _tree_kernels(compiled, capsys,
                         f"MSLR tree at {n} x {MSLR_FEATURES}")
    # five blocks of 32 groups: the result, left of the `=`
    assert sorted(re.search(r"= f32\[(\d+),160,3,256\]", ln).group(1)
                  for ln in hist) == ["1", str(WAVE_K)]
    n_pad = -(-n // 1024) * 1024
    assert _rows_on_sublanes(compiled, n) == []
    assert _rows_on_sublanes(compiled, n_pad) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < MSLR_TREE_TEMP_CEILING
    tree_bytes = mem.temp_size_in_bytes + mem.argument_size_in_bytes

    # the gradient program at the cell's layout (the benchmark's own sizes)
    spec = importlib.util.spec_from_file_location(
        "bench_data_rank", pathlib.Path(__file__).resolve().parent.parent
        / "benchmark" / "data_rank.py")
    data_rank = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(data_rank)
    sizes = data_rank.query_sizes(n, MSLR_QUERIES, rng)
    md = Metadata(n)
    md.set_label(rng.integers(0, 5, n).astype(np.float64))
    md.set_query(sizes)
    obj = create_objective("lambdarank", cfg)
    obj.init(md, n)
    assert len(obj.layout.buckets) == 9
    grad = obj._program.lower(
        on_chip((n,), jnp.float32), None, abstract(obj._per_bucket),
        abstract(obj.layout.flat_pos), None, None, None).compile()
    assert _mosaic_calls(grad) == 0
    gmem = grad.memory_analysis()
    grad_bytes = (gmem.temp_size_in_bytes + gmem.argument_size_in_bytes
                  + gmem.output_size_in_bytes)
    assert tree_bytes + grad_bytes < HBM_BYTES
    with capsys.disabled():
        print(f"AOT MSLR gradient program: temp "
              f"{gmem.temp_size_in_bytes} arguments "
              f"{gmem.argument_size_in_bytes} output "
              f"{gmem.output_size_in_bytes}; pair slots {obj.pair_slots}")
