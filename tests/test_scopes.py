"""The program's names for its own work: device scopes (`jax.named_scope`,
one `lgbm.` prefix, constants in utils/timer.py) in the lowered programs of
the training and predict hot paths, `name=` on every Pallas call, one root
host span per boosting iteration and per predict call, one flight note per
tree and per compile. docs/OBSERVABILITY.md lists what each name covers;
the chip benchmark reads device time by them (benchmark/readers/).

CPU, test size, Pallas kernels interpreted; nothing here reads a time.
"""
import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import tracing
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.models import resident as resident_mod
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import predict as predict_mod
from lightgbm_tpu.ops.compact_pallas import (COMPACT_TILE, RUN_TILES_MAX,
                                             max_pairs_bound)
from lightgbm_tpu.ops.hist_pallas import DEFAULT_TILE_ROWS
from lightgbm_tpu.parallel import learners as learners_mod
from lightgbm_tpu.parallel.learners import DeviceDataParallelTreeLearner
from lightgbm_tpu.treelearner import device as device_mod
from lightgbm_tpu.treelearner import serial as serial_mod
from lightgbm_tpu.treelearner.device import DeviceTreeLearner
from lightgbm_tpu.utils import profile, timer
from lightgbm_tpu.utils.timer import global_timer

PACKAGE = pathlib.Path(lgb.__file__).parent
SCOPES = {name: value for name, value in vars(timer).items()
          if name.startswith("SCOPE_") and name != "SCOPE_PREFIX"}
TREE_SCOPES = ["tree_setup", "select", "route", "compact", "hist", "scan",
               "replay", "commit", "finish"]
PREDICT_SCOPES = ["node_gather", "feature_gather", "decide", "leaf_values",
                  "accumulate"]
DENSE_PREDICT_SCOPES = ["feature_gather", "decide", "path_match",
                        "leaf_values", "accumulate"]
PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1}


class _Lowered(Exception):
    """Carries a program's lowered text out of the learner's dispatch."""


def _lower_instead_of_running(fn, *_):
    def dispatch(*args, **kwargs):
        raise _Lowered(fn.lower(*args, **kwargs).as_text(debug_info=True))

    return dispatch


def _data(n=1500, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 8)
    return X, (X[:, 0] - 0.7 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(float)


def _booster(learner_cls=DeviceTreeLearner, n=1500):
    X, y = _data(n)
    cfg = Config(PARAMS)
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    bst.tree_learner = learner_cls(cfg, ds)
    return bst, X


def _dispatched_program(monkeypatch, module, learner_cls) -> str:
    """The lowered text of the whole-tree program the learner dispatches
    for its first tree, with the learner's own arguments."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(module.sanitize, "guard", _lower_instead_of_running)
    bst, _ = _booster(learner_cls)
    with pytest.raises(_Lowered) as caught:
        bst.train_one_iter()
    return str(caught.value)


@pytest.fixture(scope="module")
def tree_program():
    mp = pytest.MonkeyPatch()
    try:
        return _dispatched_program(mp, device_mod, DeviceTreeLearner)
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def predict_program():
    X, y = _data(600)
    bst = lgb.train(dict(PARAMS, num_leaves=7), lgb.Dataset(X, label=y),
                    num_boost_round=3)
    packed = bst._gbdt._packed()
    return predict_mod._predict_raw_fused.lower(
        packed, jnp.asarray(X, jnp.float32), 1).as_text(debug_info=True)


@pytest.fixture(scope="module")
def dense_predict_program():
    """The TPU's gather-free program for the same forest: pack_ensemble
    asks on_tpu(), the CPU in this process, so the test answers for it."""
    X, y = _data(600)
    bst = lgb.train(dict(PARAMS, num_leaves=7), lgb.Dataset(X, label=y),
                    num_boost_round=3)
    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(predict_mod, "on_tpu", lambda: True)
        packed = predict_mod.pack_ensemble(bst._gbdt.models)
    finally:
        mp.undo()
    assert packed.dense
    return predict_mod.fused_program(packed).lower(
        packed, jnp.asarray(X, jnp.float32), 1).as_text(debug_info=True)


# ------------------------------------------------------------ device scopes


@pytest.mark.parametrize("scope", TREE_SCOPES)
def test_whole_tree_program_carries_the_scope(tree_program, scope):
    assert SCOPES["SCOPE_" + scope.upper()] in tree_program


def test_sharded_program_puts_its_collectives_under_allreduce(monkeypatch):
    text = _dispatched_program(monkeypatch, learners_mod,
                               DeviceDataParallelTreeLearner)
    assert timer.SCOPE_ALLREDUCE in text
    assert timer.SCOPE_HIST in text


COLLECTIVE = re.compile(
    r'"?stablehlo\.(all_reduce|reduce_scatter|all_gather|all_to_all|'
    r'collective_permute)"?\(')
LOC_REF = re.compile(r"loc\((#loc\d+)\)\s*$")


def _collective_name_stacks(text: str) -> list:
    """[(collective, name stack)] of a lowered program printed with
    debug_info: an operation's location closes its line, or the line that
    closes its reduction's region."""
    table = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))
    lines = text.splitlines()
    out = []
    for at, line in enumerate(lines):
        hit = COLLECTIVE.search(line)
        if not hit:
            continue
        if line.rstrip().endswith("({"):  # the region's close carries it
            indent = len(line) - len(line.lstrip())
            line = next(ln for ln in lines[at + 1:]
                        if ln.startswith(" " * indent + "})"))
        ref = LOC_REF.search(line)
        assert ref, line
        out.append((hit.group(1), table[ref.group(1)]))
    return out


@pytest.mark.parametrize("learner_cls,collectives", [
    (learners_mod.DeviceDataParallelTreeLearner,
     {"all_reduce", "reduce_scatter", "all_gather"}),
    (learners_mod.VotingDataParallelTreeLearner,
     {"all_reduce", "all_gather"}),
    (learners_mod.DeviceFeatureParallelTreeLearner, {"all_gather"}),
], ids=["data", "voting", "feature"])
def test_every_collective_of_a_sharded_program_carries_allreduce(
        monkeypatch, learner_cls, collectives):
    """Not one of them outside the scope: the chip benchmark's collective
    time (`train_4chip.allreduce_ms_per_tree`) is the scope's self time."""
    stacks = _collective_name_stacks(
        _dispatched_program(monkeypatch, learners_mod, learner_cls))
    assert {kind for kind, _ in stacks} == collectives
    for kind, stack in stacks:
        assert timer.SCOPE_ALLREDUCE + "/" in stack, (kind, stack)


def test_single_device_program_has_no_allreduce(tree_program):
    """sharded=False prunes every collective from the trace, and the scope
    with them."""
    assert timer.SCOPE_ALLREDUCE not in tree_program


def test_gradient_program_carries_its_scope():
    bst, _ = _booster()
    text = bst._grad_fn.lower(bst.score[0]).as_text(debug_info=True)
    assert timer.SCOPE_GRADIENTS in text


def test_score_update_program_carries_its_scope():
    L, n = 15, 64
    text = gbdt_mod._apply_split_log_to_score.lower(
        jnp.zeros(n, jnp.float32),
        jnp.zeros((L - 1, device_mod.STORE), jnp.float32),
        jnp.zeros(n, jnp.int32), jnp.float32(0.1),
        num_leaves=L).as_text(debug_info=True)
    assert timer.SCOPE_UPDATE_SCORE in text


# The sync path's per-tree dispatches outside the whole-tree program: each an
# eager sequence until PR 36, whose device time ran under no scope.
PER_TREE_PROGRAMS = {
    "sync_score_update": (
        lambda: gbdt_mod._add_leaf_values_to_score.lower(
            jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.int32),
            jnp.zeros(15, jnp.float32)), timer.SCOPE_UPDATE_SCORE),
    "pack_gh": (
        lambda: gbdt_mod._pack_gh.lower(
            jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.float32)),
        timer.SCOPE_TREE_SETUP),
    "leaf_ids_without_padding": (
        lambda: learners_mod._without_row_padding.lower(
            jnp.zeros(64, jnp.int32), num_data=60), timer.SCOPE_FINISH),
    # rows resident in the sharded learner's layout (PR 37): the update is
    # the two programs above over sharded operands; these are the rest
    "resident_gradients": (
        lambda: _row_programs()[1].gradients.lower(
            jnp.zeros(64, jnp.float32),
            {"_sign": jnp.ones(64, jnp.float32),
             "_lw": jnp.ones(64, jnp.float32)}), timer.SCOPE_GRADIENTS),
    "resident_pack": (
        lambda: _row_programs()[1].pack.lower(
            jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.float32)),
        timer.SCOPE_TREE_SETUP),
    "resident_root_leaf_ids": (
        lambda: learners_mod._root_leaf_ids.lower(
            60, 64, _row_programs()[0].rows), timer.SCOPE_TREE_SETUP),
    "resident_score_view": (
        lambda: _row_programs()[1].cut.lower(jnp.zeros(64, jnp.float32)),
        timer.SCOPE_FINISH),
}


def _row_programs():
    """60 rows padded to 64 over four of the CPU devices, and the binary
    objective's programs in that layout."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    layout = learners_mod.RowLayout(60, 64, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data")))
    cfg = Config(PARAMS)
    return layout, resident_mod.row_programs(
        layout, create_objective(cfg.objective, cfg))


@pytest.mark.parametrize("name", sorted(PER_TREE_PROGRAMS))
def test_a_per_tree_dispatch_is_one_program_under_a_scope(name):
    lower, scope = PER_TREE_PROGRAMS[name]
    assert scope in lower().as_text(debug_info=True)


def _scoped_copy(plane):
    with jax.named_scope(timer.SCOPE_TREE_SETUP):
        return jnp.copy(plane)


def _eager_score_update(score, ids, leaf_values, num_leaves):
    """The sync path's update as it was dispatched until PR 36, primitive
    by primitive, on the tree's own leaf count."""
    lv = jnp.asarray(leaf_values[:num_leaves], dtype=jnp.float32)
    return score + jnp.where(
        ids >= 0, lv[jnp.clip(ids, 0, num_leaves - 1)], 0.0)


def test_sync_trees_score_bit_equal_to_the_eager_update_and_compile_once(
        monkeypatch):
    """learning_rate 0.1 is not exact in float32, so every tree takes the
    sync path (as in every benchmark cell); bagging leaves rows with leaf
    id -1, which add nothing."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    X, y = _data(1500)
    cfg = Config(dict(PARAMS, learning_rate=0.1, bagging_fraction=0.7,
                      bagging_freq=1))
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    bst.tree_learner = DeviceTreeLearner(cfg, ds)
    assert not bst._async_enabled()
    program = gbdt_mod._add_leaf_values_to_score
    calls = []

    def recorded(score, ids, lv):
        out = program(score, ids, lv)
        calls.append((score, ids, np.asarray(lv), out))
        return out

    monkeypatch.setattr(gbdt_mod, "_add_leaf_values_to_score", recorded)
    for it in range(3):
        compiled = [program._cache_size(), gbdt_mod._pack_gh._cache_size()]
        notes = len(_compile_notes())
        assert not bst.train_one_iter()
        if it == 2:  # one program each, whatever the tree's leaf count
            assert [program._cache_size(),
                    gbdt_mod._pack_gh._cache_size()] == compiled
            assert len(_compile_notes()) == notes
    assert len(calls) == 3
    for (score, ids, lv, out), tree in zip(calls, bst.models):
        assert lv.shape == (cfg.num_leaves,) and lv.dtype == np.float32
        bagged_out = np.asarray(ids) < 0
        assert 0 < bagged_out.sum() < bagged_out.size
        want = _eager_score_update(score, ids, lv, tree.num_leaves)
        np.testing.assert_array_equal(np.asarray(out).view(np.uint32),
                                      np.asarray(want).view(np.uint32))
        np.testing.assert_array_equal(np.asarray(out)[bagged_out],
                                      np.asarray(score)[bagged_out])
        assert (np.asarray(out) != np.asarray(score)).any()


def test_the_plane_survives_the_tree_that_donates_its_copy(monkeypatch):
    """The whole-tree program donates its plane argument, so the learner
    hands it a per-tree copy. The copy stays an eager jnp.copy: in a jitted
    function it lowers to nothing, and the copy the compiler then makes of
    the result has no name stack for a scope to be in."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    bst, _ = _booster()
    plane = bst.tree_learner.bins_dev
    was = np.asarray(plane).copy()
    handed = []
    grow = device_mod.grow_tree_on_device
    monkeypatch.setattr(
        device_mod, "grow_tree_on_device",
        lambda bins, *a, **k: handed.append(bins) or grow(bins, *a, **k))
    scoped = jax.jit(_scoped_copy).lower(plane).as_text(debug_info=True)
    assert timer.SCOPE_TREE_SETUP not in scoped
    assert not bst.train_one_iter()
    # a second buffer, not the resident one handed over
    assert handed[0] is not plane
    assert handed[0].is_deleted() or (
        handed[0].unsafe_buffer_pointer() != plane.unsafe_buffer_pointer())
    assert bst.tree_learner.bins_dev is plane and not plane.is_deleted()
    np.testing.assert_array_equal(np.asarray(plane), was)


@pytest.mark.parametrize("stochastic", [True, False])
def test_quantize_step_is_one_program_under_its_scope(stochastic):
    """The per-tree quantization of a `use_quantized_grad` learner: the
    key's split, the discretizer, the scales and the int8 pack in ONE
    jitted program under `lgbm.quantize`."""
    from lightgbm_tpu.ops.quantize import quantize_pack

    text = quantize_pack.lower(
        jnp.zeros((65, 3), jnp.float32), jax.random.PRNGKey(1),
        num_bins=4, stochastic=stochastic).as_text(debug_info=True)
    scoped = [ln for ln in text.splitlines()
              if ln.startswith("#loc") and timer.SCOPE_QUANTIZE in ln]
    assert scoped
    for op in ("threefry_split", "discretize_gradients", "concatenate"):
        assert any(op in ln for ln in scoped), op


def test_leaf_renewal_program_carries_its_scope():
    text = device_mod._leaf_gradient_sums.lower(
        jnp.zeros((65, 3), jnp.float32), jnp.zeros(64, jnp.int32),
        num_leaves=15).as_text(debug_info=True)
    assert timer.SCOPE_RENEW_LEAVES in text


RANK_SCOPES = ["rank_sort", "rank_pairs", "rank_scatter"]
RANK_PARAMS = {"objective": "lambdarank", "metric": "ndcg",
               "eval_at": [1, 3], "num_leaves": 7, "min_data_in_leaf": 1,
               "min_sum_hessian_in_leaf": 1e-3, "verbosity": -1}


def _rank_data(queries=30, seed=5):
    """(X, grades 0..4, sizes): ragged queries of 1 to 60 documents."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(1, 61, size=queries)
    X = rng.randn(int(sizes.sum()), 8)
    grades = np.clip(np.round(X[:, 0] + 0.5 * rng.randn(len(X)) + 1), 0, 4)
    return X, grades, sizes


@pytest.fixture(scope="module")
def rank_gradient_program():
    """The one gradient program of a lambdarank objective, lowered."""
    X, grades, sizes = _rank_data()
    ds = lgb.Dataset(X, label=grades, group=sizes).construct()
    cfg = Config(RANK_PARAMS)
    obj = create_objective(cfg.objective, cfg)
    obj.init(ds._handle.metadata, len(grades))
    return jax.jit(obj.get_gradients).lower(
        jnp.zeros(len(grades), jnp.float32)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", RANK_SCOPES)
def test_lambdarank_gradient_program_nests_the_scope_under_gradients(
        rank_gradient_program, scope):
    nested = f"{timer.SCOPE_GRADIENTS}/{SCOPES['SCOPE_' + scope.upper()]}/"
    assert nested in rank_gradient_program


def test_validation_update_and_ndcg_programs_carry_their_scopes():
    text = gbdt_mod._add_valid_delta.lower(
        jnp.zeros((1, 64), jnp.float32), jnp.zeros((64, 1), jnp.float32),
        0).as_text(debug_info=True)
    assert timer.SCOPE_VALID_SCORE in text
    from lightgbm_tpu.metrics import create_metric

    X, grades, sizes = _rank_data()
    ds = lgb.Dataset(X, label=grades, group=sizes).construct()
    metric = create_metric("ndcg", Config(RANK_PARAMS))
    metric.init(ds._handle.metadata, len(grades))
    text = jax.jit(lambda s: metric._build_program()(
        s, metric._per_bucket)).lower(
        jnp.zeros(len(grades), jnp.float32)).as_text(debug_info=True)
    assert timer.SCOPE_EVAL_NDCG in text


def test_auc_is_one_program_under_its_scope():
    """AUC: the sort, the tie segments and the sums as ONE jitted program
    under lgbm.eval_auc (tests/test_categorical_device.py holds the
    categorical lane's two scopes in the whole-tree program)."""
    from lightgbm_tpu.metrics import binary as binary_mod

    score = jnp.zeros(64, jnp.float32)
    jaxpr = jax.make_jaxpr(binary_mod._auc)(score, score, None)
    assert [eqn.primitive.name for eqn in jaxpr.eqns] in (["jit"], ["pjit"])
    text = binary_mod._auc.lower(score, score, None).as_text(debug_info=True)
    assert timer.SCOPE_EVAL_AUC in text


@pytest.mark.parametrize("scope", PREDICT_SCOPES)
def test_predict_program_carries_the_scope(predict_program, scope):
    assert SCOPES["SCOPE_" + scope.upper()] in predict_program


@pytest.mark.parametrize("scope", DENSE_PREDICT_SCOPES)
def test_dense_predict_program_carries_the_scope(dense_predict_program,
                                                 scope):
    assert SCOPES["SCOPE_" + scope.upper()] in dense_predict_program


def test_dense_predict_program_has_no_node_gather(dense_predict_program):
    assert timer.SCOPE_NODE_GATHER not in dense_predict_program


def test_the_table_of_scopes_is_the_constants():
    """Every scope constant is one of the names checked above, under the
    one prefix, and no two are alike."""
    checked = set(TREE_SCOPES + PREDICT_SCOPES + DENSE_PREDICT_SCOPES
                  + ["allreduce", "gradients", "update_score", "quantize",
                     "renew_leaves", "valid_score", "eval_ndcg", "cat_scan",
                     "cat_route", "eval_auc", "bundle_scan"]
                  + RANK_SCOPES)
    assert {v for v in SCOPES.values()} == {
        timer.SCOPE_PREFIX + name for name in checked}
    assert len(set(SCOPES.values())) == len(SCOPES)


def test_scope_names_are_spelled_only_through_the_constants():
    """No string of the package but utils/timer.py's holds the prefix, and
    every named_scope takes one of the constants."""
    offenders = []
    for path in sorted(PACKAGE.rglob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and re.search(r"\blgbm\.[a-z_]+\b", node.value)
                    and path.name != "timer.py"
                    and not _is_docstring(tree, node)):
                offenders.append((path.name, node.lineno, node.value[:40]))
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope"):
                arg = node.args[0]
                if not (isinstance(arg, ast.Name) and arg.id in SCOPES):
                    offenders.append((path.name, node.lineno, "named_scope"))
    assert offenders == []


def _is_docstring(tree, constant) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if (body and isinstance(body[0], ast.Expr)
                    and body[0].value is constant):
                return True
    return False


# ----------------------------------------------------------- Pallas call names

PALLAS_CALLS = {"hist_pallas.py": ["pallas_histogram",
                                   "pallas_histogram_slots_ragged"],
                "compact_pallas.py": ["_pallas_compact_call"]}


def _pallas_call_names(path) -> list:
    names = []
    for node in ast.walk(ast.parse(path.read_text())):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            kw = {k.arg: k.value for k in node.keywords}
            assert "name" in kw, f"{path.name}:{node.lineno} has no name="
            names.append(kw["name"].value)
    return names


@pytest.mark.parametrize("filename", sorted(PALLAS_CALLS))
def test_every_pallas_call_is_named_after_its_wrapper(filename):
    assert _pallas_call_names(PACKAGE / "ops" / filename) \
        == PALLAS_CALLS[filename]


def test_no_pallas_call_site_is_left_out():
    found = {p.name: _pallas_call_names(p)
             for p in sorted((PACKAGE / "ops").glob("*.py"))}
    assert {k: v for k, v in found.items() if v} == PALLAS_CALLS


# ----------------------------------------------------------------- host spans


@pytest.fixture
def spans(monkeypatch):
    """Every closed host scope as (label, start, end), timing switched on
    for the test as LGBM_TPU_TIMETAG=1 switches it on at import."""
    seen = []
    monkeypatch.setattr(global_timer, "enabled", True)
    monkeypatch.setattr(global_timer, "span_hook",
                        lambda label, t0, t1: seen.append((label, t0, t1)))
    global_timer.new_epoch()
    yield seen
    global_timer.new_epoch()


def test_without_timetag_a_scope_opens_no_annotation_and_keeps_no_total(
        monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: opened.append(a) or (_ for _ in ()))
    monkeypatch.setattr(global_timer, "enabled", False)
    global_timer.new_epoch()
    with global_timer.scope(timer.SPAN_ITERATION):
        assert global_timer.label_stack[-1] == timer.SPAN_ITERATION
    assert opened == []
    assert timer.SPAN_ITERATION not in global_timer.totals
    assert global_timer.label_stack == []


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("boosting", ["gbdt", "dart", "rf"])
def test_three_trees_give_three_iteration_spans_that_hold_their_children(
        spans, boosting):
    """One root per boosting iteration whatever the boosting type: it is
    opened where the iteration's `train_iteration` flight span is, in the
    engine's loop, so RF (its own train_one_iter) has it too."""
    X, y = _data(600)
    params = dict(PARAMS, num_leaves=7, boosting=boosting,
                  bagging_fraction=0.8, bagging_freq=1)
    tracing.recorder().reset()
    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
    roots = [s for s in spans if s[0] == timer.SPAN_ITERATION]
    assert len(roots) == 3
    assert global_timer.counts[timer.SPAN_ITERATION] == 3
    inner = ("boosting", "bagging", "tree_train", "update_score")
    for root in roots:
        children = [s for s in spans if s[0] in inner and _inside(s, root)]
        if boosting != "rf":  # RF's own train_one_iter opens none of them
            assert set(inner) <= {c[0] for c in children}
        assert sum(c[2] - c[1] for c in children) <= root[2] - root[1]
    # nothing of the four is opened outside an iteration while training
    assert [s for s in spans if s[0] in inner
            and not any(_inside(s, r) for r in roots)] == []
    # the same interval, once: the iteration's flight span
    flight = [r for r in tracing.recorder().snapshot()
              if r["kind"] == "span" and r["name"] == "train_iteration"]
    assert len(flight) == len(roots)
    for rec, root in zip(flight, roots):
        assert rec["t0"] - 1e-5 <= root[1] and root[2] <= rec["t1"] + 1e-5


def test_a_sharded_tree_opens_its_two_host_spans_once_each(spans,
                                                           monkeypatch):
    """`shard_inputs`, `tree_device`, `gather_leaf_ids`, in that order and
    apart, inside the iteration's `tree_train`, once a tree, around what is
    left of either step now that the run's rows stay on the mesh; the
    tree's flight note says so, how wide the mesh was and what crossed it."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    X, y = _data(1500)
    tracing.recorder().reset()
    del spans[:]
    bst = lgb.train(dict(PARAMS, tree_learner="data", num_machines=4),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    learner = bst._gbdt.tree_learner
    assert type(learner) is DeviceDataParallelTreeLearner
    by_label = {label: [s for s in spans if s[0] == label]
                for label in (timer.SPAN_SHARD_INPUTS, "tree_device",
                              timer.SPAN_GATHER_LEAF_IDS, "tree_train")}
    for label in (timer.SPAN_SHARD_INPUTS, timer.SPAN_GATHER_LEAF_IDS):
        assert len(by_label[label]) == 3, label
        assert global_timer.counts[label] == 3
    for shard, device, gather in zip(by_label[timer.SPAN_SHARD_INPUTS],
                                     by_label["tree_device"],
                                     by_label[timer.SPAN_GATHER_LEAF_IDS]):
        assert shard[2] <= device[1] and device[2] <= gather[1]
        assert any(_inside(shard, t) and _inside(gather, t)
                   for t in by_label["tree_train"])
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave"]
    assert len(notes) == 3
    per_wave = global_timer.counters["device_ici_bytes_per_wave"]
    assert per_wave > 0
    shard_rows = learner.n_pad // 4
    for note in notes:
        assert note["mesh_devices"] == 4 and note["rows_resident"] == 1
        assert note["ici_bytes"] == note["waves"] * per_wave
        assert note["wave_k"] == min(learner.wave, PARAMS["num_leaves"])
        # the kernels' work is the four shards' summed: every shard walks
        # its own pairs, whatever rows of a leaf it holds
        _check_grid_steps(shard_rows, note, shards=4)
        _check_work_fields(note)


def test_a_one_chip_tree_opens_neither_sharded_span(spans, monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    bst, _ = _booster()
    assert not bst.train_one_iter()
    labels = {s[0] for s in spans}
    assert "tree_device" in labels
    assert timer.SPAN_SHARD_INPUTS not in labels
    assert timer.SPAN_GATHER_LEAF_IDS not in labels


@pytest.mark.parametrize("renew", [False, True])
def test_a_quantized_tree_opens_one_quantize_span_and_says_so_in_its_note(
        spans, monkeypatch, renew):
    """`quantize` once a tree, before `tree_device`, inside `tree_train`;
    the tree's note says `hist_int` 1 beside `hist_operand` "int"; the two
    counters count trees and rows; the learner hands over the tree's int8
    pack and scales without a copy."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    X, y = _data(1500)
    tracing.recorder().reset()
    del spans[:]
    before = {k: global_timer.counters[k]
              for k in ("quantized_trees", "quantized_rows")}
    bst = lgb.train(dict(PARAMS, use_quantized_grad=True,
                         quant_train_renew_leaf=renew),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    learner = bst._gbdt.tree_learner
    assert type(learner) is DeviceTreeLearner and learner.quantized
    quant = [s for s in spans if s[0] == timer.SPAN_QUANTIZE]
    device = [s for s in spans if s[0] == "tree_device"]
    trees = [s for s in spans if s[0] == "tree_train"]
    assert len(quant) == len(device) == 3
    for q, d in zip(quant, device):
        assert q[2] <= d[1]
        assert any(_inside(q, t) and _inside(d, t) for t in trees)
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave"]
    assert len(notes) == 3
    assert all(n["hist_int"] == 1 and n["hist_operand"] == "int"
               for n in notes)
    assert global_timer.counters["quantized_trees"] \
        - before["quantized_trees"] == 3
    assert global_timer.counters["quantized_rows"] \
        - before["quantized_rows"] == 3 * 1500
    pack, scales = learner.quant_pack()
    assert pack is learner._gh_int and pack.dtype == jnp.int8
    assert pack.shape == (1501, 3) and scales.shape == (3,)
    assert int(pack[-1].sum()) == 0 and int(pack[:-1, 2].min()) == 1


def test_a_lambdarank_iteration_opens_gradients_and_eval_valid_and_notes_it(
        spans):
    """Three iterations with a validation set: `gradients` once an
    iteration inside `boosting`, `eval_valid` once inside the iteration's
    root and after its `update_score`; one `rank_gradients` note an
    iteration with the pair counts; the two counters count them."""
    X, grades, sizes = _rank_data()
    Xv, grades_v, sizes_v = _rank_data(queries=12, seed=6)
    ds = lgb.Dataset(X, label=grades, group=sizes)
    dv = lgb.Dataset(Xv, label=grades_v, group=sizes_v, reference=ds)
    tracing.recorder().reset()
    del spans[:]
    before = {k: global_timer.counters[k]
              for k in ("rank_queries", "rank_pair_slots")}
    bst = lgb.train(RANK_PARAMS, ds, num_boost_round=3, valid_sets=[dv])
    roots = [s for s in spans if s[0] == timer.SPAN_ITERATION]
    grads = [s for s in spans if s[0] == timer.SPAN_GRADIENTS]
    evals = [s for s in spans if s[0] == timer.SPAN_EVAL_VALID]
    boosting = [s for s in spans if s[0] == "boosting"]
    updates = [s for s in spans if s[0] == "update_score"]
    assert len(roots) == len(grads) == len(evals) == 3
    for root, grad, ev, upd in zip(roots, grads, evals, updates):
        assert _inside(grad, root) and _inside(ev, root)
        assert any(_inside(grad, b) for b in boosting)
        assert upd[2] <= ev[1]
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "rank_gradients"]
    obj = bst._gbdt.objective
    m = np.minimum(sizes, 30)
    assert len(notes) == 3
    for note in notes:
        assert note["queries"] == len(sizes) and note["rows"] == len(X)
        assert note["pair_positions"] == int(
            np.sum(m * sizes - m * (m + 1) // 2)) == obj.pair_positions
        assert note["pair_slots"] == obj.pair_slots >= note["pair_positions"]
    assert global_timer.counters["rank_queries"] \
        - before["rank_queries"] == 3 * len(sizes)
    assert global_timer.counters["rank_pair_slots"] \
        - before["rank_pair_slots"] == 3 * obj.pair_slots


def test_a_float_learner_has_no_pack_and_its_note_says_hist_int_0(
        monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    tracing.recorder().reset()
    before = global_timer.counters["quantized_trees"]
    bst, _ = _booster()
    assert not bst.train_one_iter()
    bst._flush_pending()
    assert bst.tree_learner.quant_pack() is None
    note, = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave"]
    assert note["hist_int"] == 0
    assert global_timer.counters["quantized_trees"] == before


def test_a_predict_call_is_one_root_with_upload_traverse_and_fetch_once(
        spans):
    X, y = _data(600)
    bst = lgb.train(dict(PARAMS, num_leaves=7), lgb.Dataset(X, label=y),
                    num_boost_round=3)
    global_timer.new_epoch()
    del spans[:]
    bst.predict(X)
    labels = [s[0] for s in spans]
    for label in (timer.SPAN_PREDICT_CALL, timer.SPAN_PREDICT_UPLOAD,
                  timer.SPAN_PREDICT_TRAVERSE, timer.SPAN_PREDICT_FETCH):
        assert labels.count(label) == 1, (label, labels)
        assert global_timer.counts[label] == 1
    root = next(s for s in spans if s[0] == timer.SPAN_PREDICT_CALL)
    children = [s for s in spans
                if s[0] in (timer.SPAN_PREDICT_UPLOAD,
                            timer.SPAN_PREDICT_TRAVERSE,
                            timer.SPAN_PREDICT_FETCH)]
    assert all(_inside(c, root) for c in children)
    assert sum(c[2] - c[1] for c in children) <= root[2] - root[1]


def test_a_streamed_predict_opens_one_chunk_span_per_chunk(spans):
    X, y = _data(600)
    bst = lgb.train(dict(PARAMS, num_leaves=7), lgb.Dataset(X, label=y),
                    num_boost_round=3)
    del spans[:]
    out = bst.predict(X, pred_chunk_rows=256)
    np.testing.assert_array_equal(out, bst.predict(X))
    chunks = [s for s in spans[:spans.index(next(
        s for s in spans if s[0] == timer.SPAN_PREDICT_CALL)) + 1]
        if s[0] == timer.SPAN_PREDICT_CHUNK]
    assert len(chunks) == 3  # 256 + 256 + 88 rows
    for chunk in chunks:
        inner = [s[0] for s in spans if s is not chunk and _inside(s, chunk)]
        assert inner.count(timer.SPAN_PREDICT_UPLOAD) == 1
        assert inner.count(timer.SPAN_PREDICT_TRAVERSE) == 1
        assert inner.count(timer.SPAN_PREDICT_FETCH) == 1


# --------------------------------------------------------------- flight notes


@pytest.mark.parametrize("hist_f32, operand", [("0", "bf16"),
                                               ("1", "bf16x3")])
def test_one_tree_wave_note_per_tree_carries_waves_rows_and_width(
        monkeypatch, hist_f32, operand):
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("LGBM_TPU_HIST_F32", hist_f32)
    tracing.recorder().reset()
    rows_before = global_timer.counters["device_hist_rows"]
    # the whole-tree program bakes the operand in as it is traced: no
    # program of another test may stand in for this one, nor this one's
    # for a later test's
    device_mod.grow_tree_on_device.clear_cache()
    try:
        bst, _ = _booster()
        for _ in range(3):
            assert not bst.train_one_iter()
        bst._flush_pending()
    finally:
        device_mod.grow_tree_on_device.clear_cache()
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave"]
    assert len(notes) == 3
    for note, tree in zip(notes, bst.models):
        assert note["waves"] >= 1
        assert note["wave_k"] == bst.tree_learner.wave_k
        assert note["hist_rows"] >= bst.num_data  # the root pass at least
        assert note["committed"] == tree.num_leaves - 1
        assert note["speculated"] == note["waves"] * note["wave_k"]
        assert note["mesh_devices"] == 1 and note["ici_bytes"] == 0
        assert note["hist_operand"] == operand
        # the (tile, slot) pairs the histogram kernel walked: every tile
        # that holds a histogrammed row at least once, and at most once
        # more a slot of a wave (a range that starts in another's last
        # tile; a slot with no rows, visited to write its zeros)
        visits, active = note["hist_tile_visits"], note["hist_tiles_active"]
        assert note["hist_rows"] <= 1024 * active <= 1024 * visits
        assert visits <= active + note["speculated"]
        # the root's one-slot call and a call a wave, each grid walking its
        # call's live pairs; a compaction a wave, a step a run of tiles
        _check_grid_steps(2048, note)
        _check_work_fields(note)
    assert sum(n["hist_rows"] for n in notes) \
        == global_timer.counters["device_hist_rows"] - rows_before
    # the note is the one record of the kernels' work: no counter twin
    assert not [c for c in global_timer.counters
                if c.startswith(("device_hist_tile", "device_compact"))]


def _check_grid_steps(rows: int, note: dict, shards: int = 1) -> None:
    """The histogram kernel's grid walks its live pairs alone, under the
    pair table's static length T + 2K a call; the compaction kernel's walks
    one step a run of output tiles (RUN_TILES_MAX of them at these planes'
    few groups) and its live pairs, under max_pairs_bound a call, in a loop
    inside each step; for a tree of `note["waves"]` waves over `rows`
    padded rows (a shard's)."""
    t_hist, t_compact = rows // DEFAULT_TILE_ROWS, rows // COMPACT_TILE
    k = note["wave_k"]
    assert note["hist_grid_steps"] == note["hist_tile_visits"]
    assert note["hist_grid_steps"] <= shards * (
        (t_hist + 2) + note["waves"] * (t_hist + 2 * k))
    assert note["compact_grid_steps"] == shards * note["waves"] \
        * -(-t_compact // RUN_TILES_MAX)
    assert note["compact_pairs"] <= shards * note["waves"] \
        * max_pairs_bound(t_compact, 2 * k)


def _check_work_fields(note: dict) -> None:
    assert set(device_mod.WORK_FIELDS) <= set(note)
    assert len(device_mod.WORK_FIELDS) == 7
    assert 0 < note["hist_tiles_active"] <= note["hist_tile_visits"] \
        <= note["hist_grid_steps"]
    moved = note["compact_copy_pairs"] + note["compact_permute_pairs"]
    assert 0 < moved <= note["compact_pairs"]
    # a compaction step walks one pair at least: every output tile's own
    assert 0 < note["compact_grid_steps"] <= note["compact_pairs"]
    # every wave writes every tile of every shard once at least
    assert moved * COMPACT_TILE >= note["waves"] * 2048


def _compile_notes():
    return [n for n in tracing.recorder().snapshot()
            if n["kind"] == "compile"]


def test_a_first_call_leaves_one_compile_note_and_a_second_none():
    x = jnp.arange(7, dtype=jnp.float32)
    fresh = jax.jit(lambda v: (v * 3.0 + 1.0).sum())
    before = len(_compile_notes())
    fresh(x).block_until_ready()
    first = _compile_notes()[before:]
    assert len(first) == 1  # one program, one note
    assert first[0]["seconds"] > 0.0 and first[0]["cache_hit"] is False
    fresh(x).block_until_ready()
    assert len(_compile_notes()) == before + 1


def test_a_load_from_the_persistent_cache_is_one_note_marked_a_hit(tmp_path):
    """jax 0.9.0 fires the retrieval event INSIDE the backend-compile event
    on a hit: two notes would count the same seconds twice in
    `*.setup_compile_s`, which sums `seconds` over the compile notes."""
    from jax._src import compilation_cache

    flags = {"jax_enable_compilation_cache": True,
             "jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    was = {name: getattr(jax.config, name) for name in flags}
    x = jnp.arange(11, dtype=jnp.float32)

    try:
        for name, value in flags.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        before = len(_compile_notes())
        # one call site for both: the lines of the call stack are part of
        # the cache key. The first turn compiles and writes, the second loads
        for _ in range(2):
            jax.jit(lambda v: (v * 5.0 - 2.0).sum())(x).block_until_ready()
            jax.clear_caches()
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    miss, hit = _compile_notes()[before:]
    assert miss["cache_hit"] is False and hit["cache_hit"] is True
    assert miss["seconds"] > 0.0 and hit["seconds"] > 0.0


def test_the_compile_cache_is_keyed_by_names_too():
    """JAX leaves metadata out of the persistent cache's key: an executable
    compiled from another version of this source with the same arithmetic
    would bring that version's `lgbm.` scopes into a profile. One key for
    every run, so a traced run reads the executable the timed runs ran."""
    from lightgbm_tpu.utils import backend

    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        backend.configure_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, was)


def test_the_legacy_profile_variable_is_gone(monkeypatch, tmp_path):
    """LGBM_TPU_PROFILE_DIR was a second spelling nothing documented; only
    LGBM_TPU_PROFILE starts a trace."""
    started = []
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda target: started.append(target) or _null())
    monkeypatch.delenv("LGBM_TPU_PROFILE", raising=False)
    monkeypatch.setenv("LGBM_TPU_PROFILE_DIR", str(tmp_path))
    with profile.maybe_trace():
        pass
    assert started == []
    monkeypatch.setenv("LGBM_TPU_PROFILE", str(tmp_path))
    with profile.maybe_trace():
        pass
    assert started == [str(tmp_path)]


def _null():
    import contextlib

    return contextlib.nullcontext()
