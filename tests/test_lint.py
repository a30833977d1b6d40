"""graftlint: rule-family fixtures (positive snippet must flag,
negative must not), suppression/baseline mechanics, the jax-api
regression on the seed's top-level-name breakage, and the tier-1
full-tree gate (``--check`` must stay clean against the checked-in
baseline).

Pure host-side AST analysis — no device work — so everything here is
cheap even on the 2-vCPU CI host except the one subprocess CLI
contract test.
"""

import json
import os
import subprocess
import sys

import pytest

import tests._cpu  # noqa: F401  (side effect: pin CPU platform)

from hydragnn_tpu.analysis import lint_sources, run_lint, write_baseline
from hydragnn_tpu.analysis.engine import run_on_context, collect_files
from hydragnn_tpu.analysis.rules.config_schema import ConfigSchemaRule
from hydragnn_tpu.analysis.rules.host_sync import HostSyncRule
from hydragnn_tpu.analysis.rules.jax_api import JaxApiRule
from hydragnn_tpu.analysis.rules.nondet import NondetRule
from hydragnn_tpu.analysis.rules.retrace import RetraceRule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def findings_of(sources, rules):
    return lint_sources(sources, rules)


# ---------------------------------------------------------------------------
# jax-api


# The decorator idiom of the defect the seed shipped in
# hydragnn_tpu/parallel/graphshard.py (a top-level jax name that the
# installed jax keeps under jax.experimental — it broke all 7 graphshard
# tests, both giant-graph example tests, and the dryrun_graphshard entry
# leg), on a name the installed jax really lacks at top level:
# ``jax.checkify`` lives in ``jax.experimental.checkify``.
DRIFTED_API_SNIPPET = '''
from functools import partial

import jax
from jax.sharding import Mesh, PartitionSpec as P


def halo_mpnn_forward(params, shards, mesh):
    @partial(
        jax.checkify,
        errors=frozenset(),
    )
    def fwd(params, x):
        return x

    return fwd(params, shards)
'''


def test_jax_api_flags_seed_shard_map_pattern():
    f = findings_of({"pkg/graphshard.py": DRIFTED_API_SNIPPET},
                    [JaxApiRule()])
    assert len(f) == 1
    assert f[0].rule == "jax-api"
    assert "`jax.checkify` does not exist" in f[0].message
    # the relocation probe must point at the real home
    assert "jax.experimental.checkify.checkify" in f[0].message


def test_jax_api_accepts_valid_chains():
    src = '''
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.checkify import checkify
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils


def f(x):
    y = jnp.sum(x) + lax.psum(x, "i")
    jax.block_until_ready(y)
    z = jax.ops.segment_sum(x, x, num_segments=4)
    probe = getattr(jax, "checkify", None)  # getattr probes are invisible
    return jax.shard_map, jax.experimental.checkify.checkify, P(), z, probe
'''
    assert findings_of({"m.py": src}, [JaxApiRule()]) == []


def test_jax_api_flags_bad_from_import_and_aliased_chain():
    src = '''
import jax.numpy as jnp
from jax.lax import not_a_real_primitive_xyz


def f(x):
    return jnp.definitely_not_an_api_xyz(x)
'''
    f = findings_of({"m.py": src}, [JaxApiRule()])
    msgs = " | ".join(x.message for x in f)
    assert "jax.lax.not_a_real_primitive_xyz" in msgs
    assert "jax.numpy.definitely_not_an_api_xyz" in msgs


def test_jax_api_current_graphshard_is_clean():
    """Regression: the fixed graphshard module resolves everything."""
    path = os.path.join(REPO, "hydragnn_tpu/parallel/graphshard.py")
    with open(path) as fh:
        src = fh.read()
    f = findings_of({"hydragnn_tpu/parallel/graphshard.py": src},
                    [JaxApiRule()])
    assert f == []
    # and the module imports against the installed jax
    from hydragnn_tpu.parallel import graphshard

    assert callable(graphshard.sharded_mpnn_forward)


# ---------------------------------------------------------------------------
# retrace


def test_retrace_flags_fstring_of_traced_param():
    src = '''
import jax


@jax.jit
def step(x):
    label = f"value={x}"
    return x, label
'''
    f = findings_of({"m.py": src}, [RetraceRule()])
    assert any("f-string interpolates traced parameter `x`" in x.message
               for x in f)


def test_retrace_allows_loop_index_fstring():
    """params[f"filter_{i}"] over range() is idiomatic jax — the loop
    var is a Python int, not a tracer. Must NOT flag."""
    src = '''
import jax


@jax.jit
def fwd(params, x):
    for i in range(4):
        x = x @ params[f"filter_{i}"]
    return x
'''
    assert findings_of({"m.py": src}, [RetraceRule()]) == []


def test_retrace_flags_concretizing_call():
    src = '''
import jax


@jax.jit
def step(x):
    return float(x)
'''
    f = findings_of({"m.py": src}, [RetraceRule()])
    assert any("`float()` of traced parameter" in x.message for x in f)


def test_retrace_container_param_without_static():
    src = '''
import jax
from functools import partial


@jax.jit
def bad(x, cfg: dict):
    return x


@partial(jax.jit, static_argnames=("cfg",))
def good(x, cfg: dict):
    return x
'''
    f = findings_of({"m.py": src}, [RetraceRule()])
    assert len(f) == 1
    assert "`bad` takes container parameter `cfg`" in f[0].message


def test_retrace_jit_in_loop():
    src = '''
import jax


def train(fns, xs):
    out = []
    for fn in fns:
        out.append(jax.jit(fn)(xs))
    step = jax.jit(fns[0])  # hoisted: fine
    return out, step
'''
    f = findings_of({"m.py": src}, [RetraceRule()])
    assert len(f) == 1
    assert "inside a loop body" in f[0].message


def test_retrace_factory_decorator_in_loop_reported_once():
    """A @jax.jit() factory decorator on a def inside a loop is ONE
    defect — the Call branch must not double-report the decorator."""
    src = '''
import jax


def build(xs):
    out = []
    for x in xs:
        @jax.jit(donate_argnums=0)
        def step(v):
            return v + x

        out.append(step(x))
    return out
'''
    f = findings_of({"m.py": src}, [RetraceRule()])
    assert len(f) == 1
    assert "defined inside a loop body" in f[0].message


def test_retrace_loop_else_clause_not_flagged():
    """A for/while else-clause runs once after the loop — jit there is
    the hoisted pattern, not a per-iteration rebuild."""
    src = '''
import jax


def train(fns, xs):
    for fn in fns:
        pass
    else:
        step = jax.jit(fns[0])
    return step(xs)
'''
    assert findings_of({"m.py": src}, [RetraceRule()]) == []


# ---------------------------------------------------------------------------
# host-sync (call-graph reachability)

HOT_LOOP_FIXTURE = '''
import jax


def _metrics(acc):
    return acc.item()


def _cold_report(acc):
    # identical pattern, NOT reachable from the step path: no finding
    return acc.item()


def _run_epoch(step_fn, state, loader):
    acc = None
    for batch in loader:
        state, loss = step_fn(state, batch)
        acc = loss if acc is None else acc + loss
    return _metrics(acc)
'''


def test_host_sync_reachability_from_run_epoch():
    f = findings_of({"pkg/train/loop.py": HOT_LOOP_FIXTURE},
                    [HostSyncRule()])
    assert len(f) == 1
    assert "_metrics" in f[0].message and ".item()" in f[0].message


def test_host_sync_inside_jitted_flags_np():
    src = '''
import jax
import numpy as np


@jax.jit
def step(x):
    return np.asarray(x).sum()
'''
    f = findings_of({"m.py": src}, [HostSyncRule()])
    assert len(f) == 1
    assert "np.asarray" in f[0].message


def test_host_sync_reaches_nested_defs():
    """Nested helper functions are where hot-path sync calls hide —
    reachability must descend into a function's own nested defs."""
    src = '''
import jax


def _run_epoch(step_fn, state, loader):
    def _metrics(acc):
        return acc.item()

    acc = None
    for batch in loader:
        state, loss = step_fn(state, batch)
        acc = loss if acc is None else acc + loss
    return _metrics(acc)
'''
    f = findings_of({"pkg/train/loop.py": src}, [HostSyncRule()])
    assert len(f) == 1
    assert "_metrics" in f[0].message and ".item()" in f[0].message


def test_host_sync_np_in_helper_reachable_from_jit():
    """Helpers called from jitted code are inlined into the trace —
    np.asarray there is the same hard error as in the jitted body."""
    src = '''
import jax
import numpy as np


def helper(x):
    return np.asarray(x)


@jax.jit
def step(x):
    return helper(x)
'''
    f = findings_of({"m.py": src}, [HostSyncRule()])
    assert len(f) == 1
    assert "np.asarray" in f[0].message
    assert "reachable from jit-compiled code" in f[0].message


def test_host_sync_negative_plain_host_code():
    src = '''
import numpy as np


def collate(batch):
    return np.asarray(batch).item()
'''
    assert findings_of({"m.py": src}, [HostSyncRule()]) == []


# ---------------------------------------------------------------------------
# nondet

PLAN_FIXTURE = '''
import time

import numpy as np


def _order(n):
    return np.random.permutation(n)


def _seeded_order(n, seed):
    return np.random.default_rng(seed).permutation(n)


class GraphLoader:
    def epoch_plan(self, epoch):
        t0 = time.time()
        idx = _order(8)
        ok = _seeded_order(8, epoch)
        return t0, idx, ok


def host_timer():
    # not reachable from the plan: no finding
    return time.time()
'''


def test_nondet_epoch_plan_reachability():
    f = findings_of({"pkg/data/loader.py": PLAN_FIXTURE}, [NondetRule()])
    msgs = " | ".join(x.message for x in f)
    assert "`time.time()`" in msgs
    assert "np.random.permutation" in msgs
    assert "_seeded_order" not in msgs  # seeded draw is allowed
    assert "host_timer" not in msgs
    assert len(f) == 2


def test_nondet_reaches_nested_defs():
    src = '''
import time


class GraphLoader:
    def epoch_plan(self, epoch):
        def _stamp():
            return time.time()

        return _stamp()
'''
    f = findings_of({"pkg/data/loader.py": src}, [NondetRule()])
    assert len(f) == 1 and "`time.time()`" in f[0].message


def test_nondet_inside_jit():
    src = '''
import random

import jax


@jax.jit
def step(x):
    return x * random.random()
'''
    f = findings_of({"m.py": src}, [NondetRule()])
    assert len(f) == 1
    assert "random.random()" in f[0].message


# ---------------------------------------------------------------------------
# config-schema


def test_config_schema_flags_typo():
    reader = '''
def read(config):
    arch = config["NeuralNetwork"]["Architecture"]
    verbosity = config.get("Verbosity", {}).get("level", 0)
    return arch.get("hidden_dim"), verbosity
'''
    cfg = json.dumps({
        "Verbosity": {"level": 0},
        "NeuralNetwork": {"Architecture": {"hidden_dmi": 32}},
    })
    f = findings_of(
        {"pkg/reader.py": reader, "examples/a/a.json": cfg},
        [ConfigSchemaRule()],
    )
    assert len(f) == 1
    assert "`hidden_dmi`" in f[0].message
    assert "NeuralNetwork.Architecture.hidden_dmi" in f[0].message


def test_config_schema_accepts_known_and_branch_keys():
    reader = '''
def read(config):
    for split in ("train", "validate", "test"):
        _ = config["Dataset"]["path"].get(split)
    return config["NeuralNetwork"]["Training"].get("batch_size", 32)
'''
    cfg = json.dumps({
        "Dataset": {"path": {"train": "x", "test": "y"}},
        "NeuralNetwork": {"Training": {"batch_size": 8}},
        "_private": 1,
        "heads": {"branch-0": {}},
    })
    # "heads" itself unknown -> 1 finding; branch-0 and _private exempt
    f = findings_of(
        {"pkg/reader.py": reader, "tests/inputs/c.json": cfg},
        [ConfigSchemaRule()],
    )
    assert len(f) == 1 and "`heads`" in f[0].message


def test_config_schema_json_outside_scope_ignored():
    cfg = json.dumps({"totally_unknown": 1})
    assert findings_of({"bench/b.json": cfg}, [ConfigSchemaRule()]) == []


def test_config_schema_vocabulary_covers_packing_keys():
    """The Training.Parallelism.packing block (ISSUE 3 bin-packed batch
    forming) must be legal config vocabulary: the keys are harvested
    from the real reader (parallel/runtime._packing_from_config), so a
    config using them lints clean."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(REPO, ["hydragnn_tpu/parallel/runtime.py"])
    keys = harvest_accepted_keys(ctx)
    assert {
        "packing", "enabled", "max_budgets", "slack", "max_graphs"
    } <= keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Parallelism": {
                    "scheme": "single",
                    "packing": {
                        "enabled": "auto",
                        "max_budgets": 2,
                        "slack": 1.04,
                        "max_graphs": 128,
                    },
                }
            }
        }
    })
    reader = open(
        os.path.join(REPO, "hydragnn_tpu/parallel/runtime.py")
    ).read()
    f = findings_of(
        {
            "hydragnn_tpu/parallel/runtime.py": reader,
            # the schema walker needs the section names too
            "hydragnn_tpu/config/reader_stub.py": (
                'def read(c):\n'
                '    t = c["NeuralNetwork"]["Training"]\n'
                '    return t.get("Parallelism", {})\n'
            ),
            "examples/pk/pk.json": cfg,
        },
        [ConfigSchemaRule()],
    )
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_simulation_keys():
    """The top-level Simulation block (ISSUE 15 MD rollouts) must be
    legal config vocabulary: the keys are harvested from the real
    reader (simulate/engine.simulation_settings), so an example config
    carrying a rollout stanza lints clean."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(REPO, ["hydragnn_tpu/simulate/engine.py"])
    keys = harvest_accepted_keys(ctx)
    assert {
        "Simulation",
        "steps",
        "dt",
        "superstep_k",
        "temperature_k",
        "thermostat",
        "friction",
        "kb",
        "mass",
        "record_trajectory",
        "neighbor",
        "skin",
        "max_edges",
        "rebuild_policy",
        "guard",
        "max_capacity_growths",
        "capacity_growth",
        "max_dt_halvings",
        "on_nonfinite",
        "checkpoint",
        "interval_steps",
    } <= keys
    cfg = json.dumps(
        {
            "Simulation": {
                "steps": 200,
                "dt": 0.002,
                "superstep_k": 16,
                "temperature_k": 0.2,
                "thermostat": "langevin",
                "neighbor": {
                    "skin": 0.3,
                    "max_edges": 512,
                    "rebuild_policy": "displacement",
                },
                "guard": {
                    "on_nonfinite": "dt_halve",
                    "max_dt_halvings": 2,
                },
                "checkpoint": {"enabled": True, "interval_steps": 64},
            }
        }
    )
    reader = open(
        os.path.join(REPO, "hydragnn_tpu/simulate/engine.py")
    ).read()
    f = findings_of(
        {
            "hydragnn_tpu/simulate/engine.py": reader,
            "examples/sim/sim.json": cfg,
        },
        [ConfigSchemaRule()],
    )
    assert f == [], [x.message for x in f]


def test_host_sync_rollout_integrator_item_flags():
    """ISSUE 15 acceptance: an injected ``.item()`` in the integrator
    must flag — the rollout scan body is HOT_SEEDS-covered through the
    macro builder's nested defs, and the integrator functions are
    pulled in over the cross-module call edges."""
    integrator = '''
def half_kick(vel, forces, inv_m, dt):
    return vel + (0.5 * dt.item()) * forces * inv_m
'''
    engine = '''
import jax

from hydragnn_tpu.simulate.integrators import half_kick


class RolloutEngine:
    def _build_macro(self, k):
        def macro(state, dt):
            def body(st, _):
                vel = half_kick(st[0], st[1], 1.0, dt)
                return (vel, st[1]), vel

            return jax.lax.scan(body, state, None, length=k)

        return jax.jit(macro)
'''
    f = findings_of(
        {
            "hydragnn_tpu/simulate/integrators.py": integrator,
            "hydragnn_tpu/simulate/engine.py": engine,
        },
        [HostSyncRule()],
    )
    assert len(f) == 1, [x.message for x in f]
    assert "half_kick" in f[0].message and ".item()" in f[0].message


def test_host_sync_current_simulate_is_clean():
    """The shipped simulate/ package carries no unsuppressed host sync
    on the hot path (the per-macro policy fetch is the designed,
    justified exception)."""
    from hydragnn_tpu.analysis.engine import collect_files, run_on_context

    ctx = collect_files(
        REPO,
        [
            "hydragnn_tpu/simulate",
            "hydragnn_tpu/train/mlip.py",
            "hydragnn_tpu/ops/neighbors.py",
        ],
    )
    res = run_on_context(ctx, [HostSyncRule()])
    assert [f for f in res.findings if not f.suppressed] == []


# ---------------------------------------------------------------------------
# suppression + baseline mechanics


def test_suppression_same_line_next_line_file_and_all():
    base = '''
import jax


@jax.jit
def step(x):
    return float(x){SUFFIX}
'''
    flagged = findings_of({"m.py": base.replace("{SUFFIX}", "")},
                          [RetraceRule()])
    assert flagged
    same = base.replace(
        "{SUFFIX}", "  # graftlint: disable=retrace -- fixture"
    )
    assert findings_of({"m.py": same}, [RetraceRule()]) == []
    nxt = base.replace("{SUFFIX}", "").replace(
        "    return float(x)",
        "    # graftlint: disable-next-line=retrace -- fixture\n"
        "    return float(x)",
    )
    assert findings_of({"m.py": nxt}, [RetraceRule()]) == []
    allrules = base.replace(
        "{SUFFIX}", "  # graftlint: disable=all"
    )
    assert findings_of({"m.py": allrules}, [RetraceRule()]) == []
    filewide = "# graftlint: disable-file=retrace\n" + base.replace(
        "{SUFFIX}", ""
    )
    assert findings_of({"m.py": filewide}, [RetraceRule()]) == []
    # an unrelated rule name does NOT suppress
    wrong = base.replace(
        "{SUFFIX}", "  # graftlint: disable=jax-api"
    )
    assert findings_of({"m.py": wrong}, [RetraceRule()]) != []


def test_baseline_roundtrip(tmp_path):
    src_dir = tmp_path / "pkg"
    src_dir.mkdir()
    bad = src_dir / "m.py"
    bad.write_text(
        "import jax\n\n\n@jax.jit\ndef step(x):\n    return float(x)\n"
    )
    baseline = tmp_path / "baseline.json"

    res = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                   baseline_path=str(baseline))
    assert not res.ok and len(res.new) == 1

    # grandfather it -> check turns green
    write_baseline(str(baseline), res.findings)
    res2 = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                    baseline_path=str(baseline))
    assert res2.ok and len(res2.baselined) == 1 and not res2.new

    # a NEW finding is still reported even with the baseline present
    bad.write_text(
        bad.read_text() + "\n\n@jax.jit\ndef step2(y):\n    return int(y)\n"
    )
    res3 = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                    baseline_path=str(baseline))
    assert not res3.ok and len(res3.new) == 1 and len(res3.baselined) == 1

    # fixing everything leaves stale entries, detected for pruning
    bad.write_text("import jax\n")
    res4 = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                    baseline_path=str(baseline))
    assert res4.ok and len(res4.stale_baseline) == 1


def test_baseline_count_ratchet(tmp_path):
    """One grandfathered finding must NOT cover a second, new
    occurrence with the same (rule, path, message)."""
    src_dir = tmp_path / "pkg"
    src_dir.mkdir()
    bad = src_dir / "m.py"
    one = "import jax\n\n\n@jax.jit\ndef step(x):\n    return float(x)\n"
    bad.write_text(one)
    baseline = tmp_path / "baseline.json"
    res = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                   baseline_path=str(baseline))
    write_baseline(str(baseline), res.findings)
    # duplicate the offending line inside the same function: identical
    # fingerprint, second occurrence
    bad.write_text(one.replace(
        "    return float(x)\n",
        "    y = float(x)\n    return float(x)\n",
    ))
    res2 = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                    baseline_path=str(baseline))
    assert len(res2.baselined) == 1 and len(res2.new) == 1
    assert not res2.ok


def test_cli_json_marks_duplicates_by_identity(tmp_path, capsys):
    """With baseline count=1 and two identical findings, --json must
    mark exactly one as baselined (identity, not equality)."""
    cli = _load_cli()
    bad = tmp_path / "m.py"
    one = "import jax\n\n\n@jax.jit\ndef step(x):\n    return float(x)\n"
    bad.write_text(one)
    baseline = tmp_path / "baseline.json"
    # same root as the CLI (fingerprints include the relative path)
    res = run_lint(REPO, paths=[str(bad)], rules=[RetraceRule()])
    write_baseline(str(baseline), res.findings)
    bad.write_text(one.replace(
        "    return float(x)\n",
        "    y = float(x)\n    return float(x)\n",
    ))
    rc = cli.main([str(bad), "--json", "--baseline", str(baseline),
                   "--rules", "retrace"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0  # informational mode
    assert doc["new"] == 1 and doc["baselined"] == 1
    flags = sorted(e["baselined"] for e in doc["findings"])
    assert flags == [False, True]


def test_config_schema_restricted_path_run_uses_default_vocabulary():
    """`graftlint examples/x/x.json` must not flag every legitimate
    key just because no reader module is in the restricted path set."""
    res = run_lint(
        REPO,
        paths=["examples/lsms/lsms.json"],
        rules=[ConfigSchemaRule()],
        baseline_path=os.path.join(REPO, "tools/graftlint_baseline.json"),
    )
    assert res.ok, "\n".join(f.render() for f in res.new)
    assert len(res.baselined) == 1  # the grandfathered dim key


def test_line_moves_do_not_invalidate_baseline(tmp_path):
    """Fingerprints exclude line numbers: edits above a finding keep
    the baseline entry matching."""
    src_dir = tmp_path / "pkg"
    src_dir.mkdir()
    bad = src_dir / "m.py"
    body = "import jax\n\n\n@jax.jit\ndef step(x):\n    return float(x)\n"
    bad.write_text(body)
    baseline = tmp_path / "baseline.json"
    res = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                   baseline_path=str(baseline))
    write_baseline(str(baseline), res.findings)
    bad.write_text("# a new comment line\n" + body)
    res2 = run_lint(str(tmp_path), paths=["pkg"], rules=[RetraceRule()],
                    baseline_path=str(baseline))
    assert res2.ok and len(res2.baselined) == 1


# ---------------------------------------------------------------------------
# full-tree gate + CLI contract


@pytest.fixture(scope="module")
def full_tree_cli():
    """The one whole-tree lint of this file (package + examples +
    config JSONs is minutes of AST work on a loaded host): the CLI's
    `--check --json` on the default paths against the checked-in
    baseline, read by both tests below."""
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftlint.py"),
         "--check", "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=360,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_full_tree_check_is_clean(full_tree_cli):
    """The tier-1 gate: the whole package + examples + config JSONs
    must lint clean against the checked-in baseline. A regression in
    any rule family fails HERE, at commit time, instead of hours into
    a TPU run."""
    doc = json.loads(full_tree_cli.stdout)
    assert doc["ok"], "new graftlint findings:\n" + "\n".join(
        f"{e['path']}:{e['line']}: [{e['rule']}] {e['message']}"
        for e in doc["findings"] if not e["baselined"]
    )
    # the two grandfathered reference-metadata keys stay recorded
    assert not doc["stale_baseline"], (
        "baseline has stale entries — prune with "
        "`python tools/graftlint.py --write-baseline`"
    )


def test_cli_exit_code_contract(tmp_path, full_tree_cli):
    """--check exit codes: 0 on a clean tree, 1 when a new finding
    exists. One subprocess each (bounded: host-side AST work only)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bad = tmp_path / "drifted.py"
    bad.write_text("import jax\n\nx = jax.checkify\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftlint.py"),
         str(bad), "--check", "--baseline", ""],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=240,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "jax.checkify" in r.stdout
    r2 = full_tree_cli
    assert r2.returncode == 0, r2.stdout + r2.stderr
    doc = json.loads(r2.stdout)
    assert doc["ok"] is True and doc["new"] == 0


def _load_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graftlint_cli", os.path.join(REPO, "tools/graftlint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_nonexistent_path_is_usage_error(capsys):
    """A typo'd path must exit 2, not lint nothing and report green."""
    cli = _load_cli()
    rc = cli.main(["hydragnn_tpu/paralel", "--check", "--baseline", ""])
    assert rc == 2
    assert "no such file or directory" in capsys.readouterr().err


def test_cli_write_baseline_refuses_restricted_runs(capsys):
    """--write-baseline over a subset would silently drop grandfathered
    entries outside the restriction."""
    cli = _load_cli()
    assert cli.main(["hydragnn_tpu", "--write-baseline"]) == 2
    assert cli.main(["--rules", "jax-api", "--write-baseline"]) == 2
    err = capsys.readouterr().err
    assert "full default-scope run" in err


def test_jax_api_message_fingerprint_stable_across_jax_versions():
    """Finding messages must not embed the jax version — baseline
    fingerprints have to survive upgrades."""
    f = findings_of({"pkg/graphshard.py": DRIFTED_API_SNIPPET},
                    [JaxApiRule()])
    import jax

    assert jax.__version__ not in f[0].message


def test_rule_catalog_and_selection():
    from hydragnn_tpu.analysis import all_rules, rules_by_name

    names = {r.name for r in all_rules()}
    assert names == {
        "jax-api", "retrace", "host-sync", "nondet", "config-schema",
        "fp-contract", "donation", "thread-discipline", "hot-coverage",
        "suppression", "lock-order", "guarded-field",
        "barrier-discipline",
    }
    assert [r.name for r in rules_by_name(["jax-api"])] == ["jax-api"]
    with pytest.raises(ValueError):
        rules_by_name(["no-such-rule"])


def test_host_sync_superstep_scan_body_is_hot():
    """ISSUE 4: the superstep scan body is passed BY VALUE to lax.scan
    (no call edge), yet it runs K times per dispatch — hot seeds must
    pull in functions NESTED under them, so a stray .item() inside the
    body (or the jitted closure) is a lint error."""
    src = '''
import jax


def make_superstep_fn(model, tx):
    def superstep(state, acc, batches):
        def body(carry, batch):
            state, lsum = carry
            loss = model(state, batch)
            lsum = lsum + loss.item()
            return (state, lsum), None

        return jax.lax.scan(body, (state, acc), batches)

    return jax.jit(superstep, donate_argnums=(0, 1))
'''
    f = findings_of({"pkg/train/loop.py": src}, [HostSyncRule()])
    assert len(f) == 1
    assert ".item()" in f[0].message and "body" in f[0].message


def test_host_sync_real_superstep_fn_is_covered_and_clean():
    """The REAL make_superstep_fn (and its scan bodies) must be inside
    the host-sync hot set — and clean."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(REPO, ["hydragnn_tpu/train/loop.py"])
    graph = build_callgraph(ctx)
    assert any(
        graph.find(p, q) for p, q in HOT_SEEDS
        if q == "make_superstep_fn"
    ), "make_superstep_fn not found among host-sync hot seeds"
    # nested scan bodies exist in the graph under the seed's qualname
    nested = [
        k for k in graph.funcs
        if k[1].startswith("make_superstep_fn.")
    ]
    assert nested, "superstep scan bodies not registered as nested defs"
    f = findings_of(
        {"hydragnn_tpu/train/loop.py": ctx.py_files[0].text},
        [HostSyncRule()],
    )
    # the one intentional sync (trace-mode barrier) is suppressed in
    # the real file; nothing new may appear
    assert f == [], [x.message for x in f]


def test_host_sync_dp_superstep_and_epoch_driver_are_covered():
    """ISSUE 5: the dp superstep scan body (make_dp_superstep_fn) and
    the dp epoch drivers (DPLoader's plain + grouped iterators) are
    host-sync hot seeds — their nested defs register, and the real file
    stays clean."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(REPO, ["hydragnn_tpu/parallel/dp.py"])
    graph = build_callgraph(ctx)
    for qual in (
        "make_dp_superstep_fn",
        "DPLoader.__iter__",
        "DPLoader._iter_superstep",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    nested = [
        k for k in graph.funcs
        if k[1].startswith("make_dp_superstep_fn.")
    ]
    assert nested, "dp scan bodies not registered as nested defs"
    f = findings_of(
        {"hydragnn_tpu/parallel/dp.py": ctx.py_files[0].text},
        [HostSyncRule()],
    )
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_superstep_keys():
    """The Training.Parallelism.superstep block (ISSUE 4 superstep
    executor) must be legal config vocabulary: keys are harvested from
    the real reader (parallel/runtime._superstep_from_config)."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(REPO, ["hydragnn_tpu/parallel/runtime.py"])
    keys = harvest_accepted_keys(ctx)
    assert {"superstep", "steps", "max_host_bytes"} <= keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Parallelism": {
                    "scheme": "single",
                    "superstep": {
                        "steps": "auto",
                        "max_host_bytes": 268435456,
                    },
                }
            }
        }
    })
    reader = open(
        os.path.join(REPO, "hydragnn_tpu/parallel/runtime.py")
    ).read()
    f = findings_of(
        {
            "hydragnn_tpu/parallel/runtime.py": reader,
            "hydragnn_tpu/config/reader_stub.py": (
                'def read(c):\n'
                '    t = c["NeuralNetwork"]["Training"]\n'
                '    return t.get("Parallelism", {})\n'
            ),
            "examples/ss/ss.json": cfg,
        },
        [ConfigSchemaRule()],
    )
    assert f == [], [x.message for x in f]


def test_host_sync_checkpoint_writer_and_skip_to_are_covered():
    """ISSUE 6 (durability): the async CheckpointWriter's caller-thread
    save (its only legal sync is the designed snapshot barrier,
    suppressed in place) and background worker, plus the resume
    fast-forward helpers, are host-sync hot seeds — and the real files
    stay clean."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    files = [
        "hydragnn_tpu/utils/checkpoint.py",
        "hydragnn_tpu/data/loader.py",
        "hydragnn_tpu/data/pipeline.py",
    ]
    ctx = collect_files(REPO, files)
    graph = build_callgraph(ctx)
    for qual in (
        "CheckpointWriter.save",
        "CheckpointWriter._worker_main",
        "GraphLoader.skip_to",
        "drop_consumed_groups",
        "skip_delivered_items",
        "ParallelPipelineLoader.skip_to",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    sources = {
        sf.relpath: sf.text for sf in ctx.py_files
    }
    f = findings_of(sources, [HostSyncRule()])
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_checkpoint_keys():
    """The Training.Checkpoint durability block (ISSUE 6: async writer
    knobs) and Training.bn_recalibration must be legal config
    vocabulary: keys are harvested from the real readers
    (utils/checkpoint.checkpoint_settings,
    train/loop._bn_recalibration_epochs)."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    files = [
        "hydragnn_tpu/utils/checkpoint.py",
        "hydragnn_tpu/train/loop.py",
    ]
    ctx = collect_files(REPO, files)
    keys = harvest_accepted_keys(ctx)
    assert {
        "Checkpoint", "enabled", "async", "interval_steps", "retries",
        "backoff", "bn_recalibration", "epochs",
        "walltime_min_seconds_left",
    } <= keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Checkpoint": {
                    "enabled": True,
                    "async": True,
                    "interval_steps": 200,
                    "retries": 3,
                    "backoff": 0.25,
                },
                "bn_recalibration": {"enabled": True, "epochs": 1},
            }
        }
    })
    sources = {sf.relpath: sf.text for sf in ctx.py_files}
    sources["examples/ck/ck.json"] = cfg
    f = findings_of(sources, [ConfigSchemaRule()])
    assert f == [], [x.message for x in f]


def test_host_sync_telemetry_emit_paths_are_covered():
    """ISSUE 7: the run-telemetry emit paths (StepClock.record/finish,
    TelemetryStream.emit and the stream worker) are host-sync hot
    seeds; the ONLY syncs in the real file are the config-gated
    sampled fence and the one epoch-end batched fetch, both suppressed
    in place — nothing new may appear."""
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(REPO, ["hydragnn_tpu/utils/telemetry.py"])
    graph = build_callgraph(ctx)
    for qual in (
        "StepClock.record",
        "StepClock.finish",
        "TelemetryStream.emit",
        "TelemetryStream._worker_main",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    src = ctx.py_files[0].text
    # the suppressions are load-bearing: stripping them must flag both
    # the sampled fence and the epoch-end fetch
    stripped = "\n".join(
        line
        for line in src.splitlines()
        if "graftlint: disable-next-line=host-sync" not in line
    )
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": stripped}, [HostSyncRule()]
    )
    msgs = [x.message for x in f]
    assert any("block_until_ready" in m for m in msgs), msgs
    assert any("device_get" in m for m in msgs), msgs
    # and with the suppressions in place the real file is clean
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": src}, [HostSyncRule()]
    )
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_telemetry_keys():
    """The Training.Telemetry block (ISSUE 7 run telemetry) must be
    legal config vocabulary: keys are harvested from the real reader
    (utils/telemetry.telemetry_settings)."""
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(REPO, ["hydragnn_tpu/utils/telemetry.py"])
    keys = harvest_accepted_keys(ctx)
    assert {
        "Telemetry",
        "enabled",
        "stream_path",
        "sync_interval_steps",
        "rollup",
        "queue_depth",
    } <= keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Telemetry": {
                    "enabled": True,
                    "stream_path": "logs/run/telemetry.jsonl",
                    "sync_interval_steps": 16,
                    "rollup": True,
                }
            }
        }
    })
    reader = open(
        os.path.join(REPO, "hydragnn_tpu/utils/telemetry.py")
    ).read()
    f = findings_of(
        {
            "hydragnn_tpu/utils/telemetry.py": reader,
            "hydragnn_tpu/config/reader_stub.py": (
                'def read(c):\n'
                '    t = c["NeuralNetwork"]["Training"]\n'
                '    return t.get("Telemetry", {})\n'
            ),
            "examples/tel/tel.json": cfg,
        },
        [ConfigSchemaRule()],
    )
    assert f == [], [x.message for x in f]


def test_host_sync_roofline_capture_paths_are_covered():
    """ISSUE 8: the first-dispatch executable capture, the memory
    sampler and the trace-annotation helpers run on (or adjacent to)
    the step thread — all are host-sync hot seeds, so a stray
    ``.item()``/``device_get`` in any of them lints; and the REAL
    files stay clean (the capture lowers/compiles but never syncs)."""
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(
        REPO,
        ["hydragnn_tpu/utils/telemetry.py", "hydragnn_tpu/utils/tracer.py"],
    )
    graph = build_callgraph(ctx)
    for qual in (
        "StepClock._maybe_capture",
        "memory_row",
        "note_trace_step",
        "step_annotation",
        "region",
        "span",
        "scope",
        "_Region.__enter__",
        "_Region.__exit__",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    # a sync smuggled into the capture MUST flag (fixture shaped like
    # the real method, plus the forbidden call)
    bad = (
        "class StepClock:\n"
        "    def _maybe_capture(self, fn, args, spec, k):\n"
        "        compiled = fn.lower(*args).compile()\n"
        "        loss = args[0]\n"
        "        v = loss.item()\n"
        "        return compiled, v\n"
    )
    f = findings_of({"hydragnn_tpu/utils/telemetry.py": bad}, [HostSyncRule()])
    assert any(".item()" in x.message for x in f), [x.message for x in f]
    bad_tr = (
        "import jax\n"
        "def note_trace_step():\n"
        "    jax.device_get(0)\n"
    )
    f = findings_of({"hydragnn_tpu/utils/tracer.py": bad_tr}, [HostSyncRule()])
    assert any("device_get" in x.message for x in f), [x.message for x in f]
    # and into a region's entry (ISSUE 27: it opens around every site
    # of the loop's host work)
    bad_region = (
        "class _Region:\n"
        "    def __enter__(self):\n"
        "        self.loss.block_until_ready()\n"
    )
    f = findings_of(
        {"hydragnn_tpu/utils/tracer.py": bad_region}, [HostSyncRule()]
    )
    assert any("block_until_ready" in x.message for x in f), f
    # the real tracer file is clean under the rule (the telemetry
    # file's cleanliness is pinned by the ISSUE-7 test above)
    src = next(
        sf.text
        for sf in ctx.py_files
        if sf.relpath.endswith("tracer.py")
    )
    f = findings_of({"hydragnn_tpu/utils/tracer.py": src}, [HostSyncRule()])
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_profiling_and_roofline_keys():
    """The Training.Profiling block (ISSUE 8 profiler alignment) and
    the Telemetry.cost_analysis key must be legal config vocabulary,
    harvested from the REAL readers (utils/tracer.Profiler and
    utils/telemetry.telemetry_settings)."""
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(
        REPO,
        ["hydragnn_tpu/utils/tracer.py", "hydragnn_tpu/utils/telemetry.py"],
    )
    keys = harvest_accepted_keys(ctx)
    assert {
        "Profiling",
        "enabled",
        "epoch",
        "steps",
        "trace_dir",
        "cost_analysis",
    } <= keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Telemetry": {"enabled": True, "cost_analysis": True},
                "Profiling": {
                    "enabled": True,
                    "epoch": 1,
                    "steps": 20,
                    "trace_dir": "logs/run/jax_trace",
                },
            }
        }
    })
    sources = {sf.relpath: sf.text for sf in ctx.py_files}
    sources["examples/prof/prof.json"] = cfg
    f = findings_of(sources, [ConfigSchemaRule()])
    assert f == [], [x.message for x in f]


def test_host_sync_fused_edge_pipeline_is_covered_and_clean():
    """ISSUE 9: the fused edge-pipeline kernel entry points
    (edge_pipeline_planned, the kernel body, and the pallas_call
    builder whose index_map lambdas are passed by value) are host-sync
    hot seeds — nested defs register through the qualname expansion,
    and the real file stays clean."""
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(REPO, ["hydragnn_tpu/ops/pallas_segment.py"])
    graph = build_callgraph(ctx)
    for qual in (
        "edge_pipeline_planned",
        "_edge_pipeline_kernel",
        "_pallas_edge_pipeline",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    # the pallas_call builder's index_map lambdas / kernel partials are
    # nested defs under the seeds' qualnames
    nested = [
        k
        for k in graph.funcs
        if k[1].startswith(("_pallas_edge_pipeline.", "_edge_pipeline_kernel."))
    ]
    assert nested, "pallas_call nested defs not registered"
    f = findings_of(
        {"hydragnn_tpu/ops/pallas_segment.py": ctx.py_files[0].text},
        [HostSyncRule()],
    )
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_segment_and_precision_keys():
    """ISSUE 9 config surface: the bf16 precision key and the
    segment-kernel grammar (Training.use_segment_plan /
    Training.segment_impl) are legal vocabulary harvested from the
    REAL readers (runner.run_training, train/state.resolve_precision)
    — a config carrying them must lint clean."""
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(
        REPO,
        ["hydragnn_tpu/runner.py", "hydragnn_tpu/train/state.py"],
    )
    keys = harvest_accepted_keys(ctx)
    assert {"precision", "use_segment_plan", "segment_impl"} <= keys
    cfg = json.dumps(
        {
            "Training": {
                "precision": "bf16",
                "use_segment_plan": "auto",
                "segment_impl": "pallas_fused",
            }
        }
    )
    readers = {
        os.path.join("hydragnn_tpu", "runner.py"): open(
            os.path.join(REPO, "hydragnn_tpu", "runner.py")
        ).read(),
        os.path.join("hydragnn_tpu", "train", "state.py"): open(
            os.path.join(REPO, "hydragnn_tpu", "train", "state.py")
        ).read(),
        os.path.join("examples", "seg.json"): cfg,
    }
    f = findings_of(readers, [ConfigSchemaRule()])
    assert f == [], [x.message for x in f]


def test_host_sync_guard_paths_are_covered():
    """ISSUE 10: the divergence guard's traced core (guarded_commit +
    the poison helpers — by-value inside the superstep scan body, so
    the nested-def expansion matters) and the monitor's per-dispatch
    observe/check are host-sync hot seeds. A stray ``.item()`` in the
    predicate must lint; the REAL file's only sync is the designed
    resolution fetch in check(), suppressed in place — stripping the
    suppression must flag it, and the real file stays clean."""
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS, HostSyncRule

    ctx = collect_files(REPO, ["hydragnn_tpu/train/guard.py"])
    graph = build_callgraph(ctx)
    for qual in (
        "guarded_commit",
        "poison_scalar",
        "poison_tree",
        "poison_batch",
        "GuardMonitor.observe",
        "GuardMonitor.check",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    src = ctx.py_files[0].text
    stripped = "\n".join(
        line
        for line in src.splitlines()
        if "graftlint: disable-next-line=host-sync" not in line
    )
    f = findings_of(
        {"hydragnn_tpu/train/guard.py": stripped}, [HostSyncRule()]
    )
    assert any("device_get" in x.message for x in f), [
        x.message for x in f
    ]
    f = findings_of(
        {"hydragnn_tpu/train/guard.py": src}, [HostSyncRule()]
    )
    assert f == [], [x.message for x in f]
    # an injected .item() in the traced predicate flags
    poisoned = src.replace(
        "ok = jnp.isfinite(tot) & jnp.isfinite(gnorm)",
        "ok = jnp.isfinite(tot) & jnp.isfinite(gnorm)\n"
        "    _ = gnorm.item()",
    )
    assert poisoned != src
    f = findings_of(
        {"hydragnn_tpu/train/guard.py": poisoned}, [HostSyncRule()]
    )
    assert any(".item()" in x.message for x in f), [
        x.message for x in f
    ]


def test_config_schema_vocabulary_covers_guard_keys():
    """The Training.Guard block (ISSUE 10) and the new
    Checkpoint.validate_finite / Optimizer.clip_grad_norm knobs must
    be legal config vocabulary: keys harvested from the REAL readers
    (train/guard.guard_settings, utils/checkpoint.checkpoint_settings,
    train/optimizer.select_optimizer)."""
    from hydragnn_tpu.analysis.rules.config_schema import (
        ConfigSchemaRule,
        harvest_accepted_keys,
    )

    files = [
        "hydragnn_tpu/train/guard.py",
        "hydragnn_tpu/utils/checkpoint.py",
        "hydragnn_tpu/train/optimizer.py",
    ]
    ctx = collect_files(REPO, files)
    keys = harvest_accepted_keys(ctx)
    assert {
        "Guard",
        "enabled",
        "policy",
        "max_bad_steps",
        "window_steps",
        "check_interval_steps",
        "lr_backoff",
        "max_rollbacks",
        "validate_finite",
        "clip_grad_norm",
    } <= keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Guard": {
                    "enabled": True,
                    "policy": "rollback",
                    "max_bad_steps": 2,
                    "window_steps": 200,
                    "check_interval_steps": 50,
                    "lr_backoff": 0.5,
                    "max_rollbacks": 2,
                },
                "Checkpoint": {"enabled": True, "validate_finite": True},
                "Optimizer": {"clip_grad_norm": 1.0},
            }
        }
    })
    sources = {sf.relpath: sf.text for sf in ctx.py_files}
    sources["hydragnn_tpu/config/reader_stub.py"] = (
        'def read(c):\n'
        '    t = c["NeuralNetwork"]["Training"]\n'
        '    return t.get("Guard", {})\n'
    )
    sources["examples/guard/guard.json"] = cfg
    f = findings_of(sources, [ConfigSchemaRule()])
    assert f == [], [x.message for x in f]


# ---------------------------------------------------------------------------
# ISSUE 12: fp-contract


SCAN_FMA_FIXTURE = '''
import jax
import jax.numpy as jnp


def fold(acc, prods, gs):
    def body(carry, xs):
        lsum, ng = carry
        p, g = xs
        # the injected fault: a fusable multiply-add in the scan body
        lsum = lsum + p * g
        return (lsum, ng + g), None

    acc, _ = jax.lax.scan(body, acc, (prods, gs))
    return acc
'''


def test_fp_contract_flags_fma_in_scan_body():
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    f = findings_of({"pkg/train/loop.py": SCAN_FMA_FIXTURE},
                    [FpContractRule()])
    assert len(f) == 1
    assert "fusable multiply-add" in f[0].message
    assert "body" in f[0].message


def test_fp_contract_multiply_free_accumulation_is_clean():
    """The sanctioned idiom — products rounded outside, add-only scan
    body — must NOT flag (the real fold_step_metrics shape)."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    src = '''
import jax


def fold(acc, tots, gs):
    prods = tots * gs

    def body(carry, xs):
        lsum, ng = carry
        p, g = xs
        return (lsum + p, ng + g), None

    acc, _ = jax.lax.scan(body, acc, (prods, gs))
    return acc
'''
    assert findings_of({"pkg/train/loop.py": src},
                       [FpContractRule()]) == []


def test_fp_contract_flags_additive_identity_in_bitwise_seed():
    """x + 0.0 inside a bitwise-contract seed (poison_scalar's module
    position) flags with the select-not-add guidance."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    src = '''
import jax.numpy as jnp


def poison_scalar(rules, site, step, x):
    return x + 0.0
'''
    f = findings_of({"pkg/train/guard.py": src}, [FpContractRule()])
    assert len(f) == 1
    assert "additive identity" in f[0].message
    assert "select-not-add" in f[0].message


def test_fp_contract_ignores_code_outside_scope():
    """The same a*b+c in a plain host function (no scan, no seed) is
    legal float arithmetic — must not flag."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    src = '''
def metric(a, b, c):
    return a * b + c + 0.0
'''
    assert findings_of({"pkg/utils/misc.py": src},
                       [FpContractRule()]) == []


def test_fp_contract_reaches_scan_body_helpers():
    """A helper CALLED from the scan body fuses into the same loop —
    reachability must extend beyond the body function itself."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    src = '''
import jax


def rescale(l, corr, s):
    return l * corr + s


def scan_fn(carry, xs):
    l, corr, s = xs
    return rescale(l, corr, s), None


def run(init, xs):
    return jax.lax.scan(scan_fn, init, xs)
'''
    f = findings_of({"pkg/ops/attn.py": src}, [FpContractRule()])
    assert len(f) == 1 and "rescale" in f[0].message


def test_fp_contract_real_superstep_and_guard_are_clean():
    """The real bitwise-contract surfaces lint clean: the superstep
    builders, fold_step_metrics and the guard's traced core all hold
    the multiply-free / select-not-add discipline."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    files = [
        "hydragnn_tpu/train/loop.py",
        "hydragnn_tpu/train/guard.py",
        "hydragnn_tpu/parallel/dp.py",
    ]
    ctx = collect_files(REPO, files)
    sources = {sf.relpath: sf.text for sf in ctx.py_files}
    f = findings_of(sources, [FpContractRule()])
    assert f == [], [x.render() for x in f]


def test_fp_contract_ring_attention_suppressions_load_bearing():
    """The ring-attention online-softmax rescales are DESIGNED
    mul+adds, suppressed in place — stripping the suppressions must
    flag both accumulator updates."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    path = os.path.join(REPO, "hydragnn_tpu/parallel/graphshard.py")
    src = open(path).read()
    rel = "hydragnn_tpu/parallel/graphshard.py"
    assert findings_of({rel: src}, [FpContractRule()]) == []
    stripped = "\n".join(
        line for line in src.splitlines()
        if "graftlint: disable-next-line=fp-contract" not in line
    )
    f = findings_of({rel: stripped}, [FpContractRule()])
    assert len(f) == 2, [x.render() for x in f]
    assert all("fusable multiply-add" in x.message for x in f)


# ---------------------------------------------------------------------------
# ISSUE 12: donation


DONATION_FIXTURE = '''
import jax


def loop(step, state, acc, batches):
    jit_step = jax.jit(step, donate_argnums=(1,))
    for batch in batches:
        state, loss = jit_step(state, acc)
    return state, acc  # the injected fault: acc was donated
'''


def test_donation_flags_read_after_donated_call():
    from hydragnn_tpu.analysis.rules.donation import DonationRule

    f = findings_of({"pkg/train/loop.py": DONATION_FIXTURE},
                    [DonationRule()])
    assert len(f) == 1
    assert "`acc` was donated" in f[0].message
    assert "PR-7" in f[0].message


def test_donation_rebind_is_clean():
    """The sanctioned idiom — rebinding every donated name from the
    return value — must NOT flag (the universal loop shape here)."""
    from hydragnn_tpu.analysis.rules.donation import DonationRule

    src = '''
import jax


def loop(step, state, acc, batches):
    jit_step = jax.jit(step, donate_argnums=(0, 1))
    for batch in batches:
        state, acc = jit_step(state, acc)
    return state, acc
'''
    assert findings_of({"pkg/train/loop.py": src},
                       [DonationRule()]) == []


def test_donation_tracks_decorated_functions():
    from hydragnn_tpu.analysis.rules.donation import DonationRule

    src = '''
from functools import partial

import jax


@partial(jax.jit, donate_argnums=0)
def step(state, batch):
    return state


def drive(state, batch):
    new = step(state, batch)
    return new, state.params
'''
    f = findings_of({"pkg/m.py": src}, [DonationRule()])
    assert len(f) == 1 and "`state` was donated" in f[0].message


def test_donation_tracks_builder_returns():
    """Donation must follow the dominant shape here: a builder whose
    return statement is jax.jit(inner, donate_argnums=...) — the
    caller never sees a jit call."""
    from hydragnn_tpu.analysis.rules.donation import DonationRule

    src = '''
import jax


def make_step(model):
    def step(state, batch):
        return state

    return jax.jit(step, donate_argnums=0)


def drive(model, state, batches):
    fn = make_step(model)
    for b in batches:
        out = fn(state, b)
    return state  # donated on the first call, then read
'''
    f = findings_of({"pkg/m.py": src}, [DonationRule()])
    assert len(f) == 1 and "`state` was donated" in f[0].message
    assert "make_step" in f[0].message


def test_donation_real_tree_is_clean():
    """Every real loop rebinds its donated names — the production
    train/serve/parallel surfaces carry zero donation findings."""
    from hydragnn_tpu.analysis.rules.donation import DonationRule

    files = [
        "hydragnn_tpu/train/loop.py",
        "hydragnn_tpu/parallel/dp.py",
        "hydragnn_tpu/parallel/multibranch.py",
        "hydragnn_tpu/serve/engine.py",
        "hydragnn_tpu/utils/telemetry.py",
    ]
    ctx = collect_files(REPO, files)
    sources = {sf.relpath: sf.text for sf in ctx.py_files}
    f = findings_of(sources, [DonationRule()])
    assert f == [], [x.render() for x in f]


# ---------------------------------------------------------------------------
# ISSUE 12: thread-discipline


NEVER_BLOCK_FIXTURE = '''
import queue


class TelemetryStream:
    def __init__(self):
        self._q = queue.Queue(maxsize=4)

    def emit(self, row):
        self._q.put(row)
        return True
'''


def test_thread_discipline_flags_put_in_never_block_path():
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    f = findings_of({"pkg/utils/telemetry.py": NEVER_BLOCK_FIXTURE},
                    [ThreadDisciplineRule()])
    assert len(f) == 1
    assert "blocking `.put(...)`" in f[0].message
    assert "put_nowait" in f[0].message


def test_thread_discipline_put_nowait_and_cold_code_clean():
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = '''
import queue
import time


class TelemetryStream:
    def __init__(self):
        self._q = queue.Queue(maxsize=4)

    def emit(self, row):
        try:
            self._q.put_nowait(row)
        except queue.Full:
            return False
        return True


def cold_path(q, t):
    q.put(1)        # not reachable from a never-block seed
    time.sleep(t)   # ditto
    t.join()
'''
    assert findings_of({"pkg/utils/telemetry.py": src},
                       [ThreadDisciplineRule()]) == []


def test_thread_discipline_flags_wait_join_sleep_open():
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = '''
import time


def _run_epoch(step_fn, state, loader, ev, worker):
    ev.wait()
    worker.join()
    time.sleep(0.1)
    with open("/tmp/x", "w") as f:
        f.write("row")
    ev.wait(timeout=1.0)  # bounded: fine
    ", ".join(["a"])      # str.join takes an arg: fine
    return state
'''
    f = findings_of({"pkg/train/loop.py": src}, [ThreadDisciplineRule()])
    kinds = sorted(x.message.split("`")[1] for x in f)
    assert len(f) == 4, [x.render() for x in f]
    assert any("unbounded `.wait()`" in x.message for x in f)
    assert any("unbounded `.join()`" in x.message for x in f)
    assert any("time.sleep" in x.message for x in f)
    assert any("sync file I/O" in x.message for x in f)


def test_thread_discipline_worker_without_finally_flags():
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = '''
import threading


class Writer:
    def __init__(self):
        self._thread = threading.Thread(target=self._main, daemon=True)
        self._thread.start()

    def _main(self):
        pass

    def close(self):
        pass


def trial(cfg):
    w = Writer()
    w.close()          # not in a finally: an exception above leaks it
    return cfg


def good_trial(cfg):
    w = Writer()
    try:
        return cfg
    finally:
        w.close()


def factory():
    w = Writer()
    return w           # ownership escapes: caller owns teardown


class Owner:
    def __init__(self):
        self.w = Writer()   # ownership escapes to the instance
'''
    f = findings_of({"pkg/utils/writer.py": src},
                    [ThreadDisciplineRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "without close()/stop() in a finally" in f[0].message
    assert "`trial`" in f[0].message


def test_thread_discipline_worker_class_without_closer_flags():
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = '''
import threading


class Leaky:
    def start(self):
        self._thread = threading.Thread(target=self._main)
        self._thread.start()

    def _main(self):
        pass
'''
    f = findings_of({"pkg/utils/leaky.py": src},
                    [ThreadDisciplineRule()])
    assert len(f) == 1
    assert "defines no close()/stop()/shutdown()" in f[0].message


def test_thread_discipline_generator_scoped_threads_not_workers():
    """PrefetchLoader-style threads — local to a generator that tears
    them down in its own finally — are NOT persistent workers; the
    close-in-finally contract does not apply."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    ctx = collect_files(
        REPO,
        ["hydragnn_tpu/data/prefetch.py", "hydragnn_tpu/data/pipeline.py"],
    )
    sources = {sf.relpath: sf.text for sf in ctx.py_files}
    f = findings_of(sources, [ThreadDisciplineRule()])
    assert f == [], [x.render() for x in f]


def test_thread_discipline_real_checkpoint_suppressions_load_bearing():
    """The checkpoint writer's designed stalls (single-writer
    backpressure, the cv barrier, the sync-fallback writes, retry
    backoff) are suppressed in place — the real file is clean, and
    stripping the suppressions must flag them."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    rel = "hydragnn_tpu/utils/checkpoint.py"
    src = open(os.path.join(REPO, rel)).read()
    assert findings_of({rel: src}, [ThreadDisciplineRule()]) == []
    stripped = "\n".join(
        line for line in src.splitlines()
        if "graftlint: disable-next-line=thread-discipline" not in line
    )
    f = findings_of({rel: stripped}, [ThreadDisciplineRule()])
    msgs = [x.message for x in f]
    assert any("unbounded `.wait()`" in m for m in msgs), msgs
    assert any("sync file I/O" in m for m in msgs), msgs
    assert any("time.sleep" in m for m in msgs), msgs


def test_thread_discipline_real_batcher_submit_never_blocks():
    """Regression for the fixed hazard: DynamicBatcher.submit must use
    put_nowait (an injected plain put flags)."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    rel = "hydragnn_tpu/serve/batcher.py"
    src = open(os.path.join(REPO, rel)).read()
    assert "self._q.put_nowait(req)" in src
    assert findings_of({rel: src}, [ThreadDisciplineRule()]) == []
    poisoned = src.replace(
        "self._q.put_nowait(req)", "self._q.put(req)"
    )
    f = findings_of({rel: poisoned}, [ThreadDisciplineRule()])
    assert any("blocking `.put(...)`" in x.message for x in f)


# ---------------------------------------------------------------------------
# ISSUE 12: hot-coverage ratchet


RATCHET_FIXTURE = '''
import jax


def make_shiny_step(model):
    @jax.jit
    def step(state, batch):
        return state

    return step


def run_training(config):
    fn = make_shiny_step(config)
    return fn
'''


def test_hot_coverage_flags_uncovered_jit_entry():
    """A jitted entry point reachable from run_training but absent
    from HOT_SEEDS fails the ratchet (the forgotten-append class)."""
    from hydragnn_tpu.analysis.rules.hot_coverage import HotCoverageRule

    f = findings_of({"pkg/runner.py": RATCHET_FIXTURE},
                    [HotCoverageRule()])
    assert len(f) == 1
    assert "make_shiny_step.step" in f[0].message
    assert "HOT_SEEDS" in f[0].message


def test_hot_coverage_seeded_builder_is_covered():
    """Nesting under a HOT_SEEDS-matched builder counts as covered —
    the existing seeding convention."""
    from hydragnn_tpu.analysis.rules.hot_coverage import HotCoverageRule

    src = RATCHET_FIXTURE.replace("make_shiny_step", "make_train_step")
    # the builder name matches the real ('train/loop.py',
    # 'make_train_step') seed only with the right path suffix
    f = findings_of({"pkg/train/loop.py": (
        "import jax\n\n\ndef make_train_step(model):\n"
        "    @jax.jit\n    def step(state, batch):\n"
        "        return state\n\n    return step\n"
    ), "pkg/runner.py": (
        "from pkg.train.loop import make_train_step\n\n\n"
        "def run_training(config):\n"
        "    return make_train_step(config)\n"
    )}, [HotCoverageRule()])
    assert f == [], [x.render() for x in f]


def test_hot_coverage_unreachable_jit_not_flagged():
    """A jitted function nobody reaches from an entry point is not the
    ratchet's business (host-sync still scans it via the jit seeds)."""
    from hydragnn_tpu.analysis.rules.hot_coverage import HotCoverageRule

    src = '''
import jax


@jax.jit
def orphan(x):
    return x


def run_training(config):
    return config
'''
    assert findings_of({"pkg/runner.py": src}, [HotCoverageRule()]) == []


def test_hot_coverage_real_tree_is_covered():
    """The ratchet holds on the real tree: every jitted function
    reachable from run_training / run_prediction / ServingEngine is
    HOT_SEEDS-covered or explicitly exempted."""
    from hydragnn_tpu.analysis.rules.hot_coverage import HotCoverageRule

    res = run_lint(REPO, rules=[HotCoverageRule()], baseline_path=None)
    assert res.findings == [], [x.render() for x in res.findings]


def test_hot_coverage_exemption_requires_reason():
    """The exemption grammar is (path, qualname) -> reason; every
    entry must carry a non-empty reason string."""
    from hydragnn_tpu.analysis.rules.hot_coverage import HOT_EXEMPT

    for (path, qual), reason in HOT_EXEMPT.items():
        assert isinstance(reason, str) and reason.strip(), (path, qual)


def test_hot_coverage_ratchet_catches_hot_seed_removal():
    """Deleting a HOT_SEEDS entry re-opens coverage findings — the
    ratchet direction (coverage can only grow)."""
    from hydragnn_tpu.analysis.rules import host_sync
    from hydragnn_tpu.analysis.rules.hot_coverage import HotCoverageRule

    kept = host_sync.HOT_SEEDS
    try:
        host_sync.HOT_SEEDS = tuple(
            s for s in kept if s[1] != "make_train_step"
        )
        res = run_lint(REPO, rules=[HotCoverageRule()],
                       baseline_path=None)
        assert any(
            "make_train_step.train_step" in x.message for x in res.findings
        ), [x.render() for x in res.findings]
    finally:
        host_sync.HOT_SEEDS = kept


# ---------------------------------------------------------------------------
# ISSUE 12: suppression hygiene + --diff / --explain


def test_bare_suppression_flags_and_justified_does_not():
    from hydragnn_tpu.analysis.rules.suppression import SuppressionRule

    bare = '''
import jax


@jax.jit
def step(x):
    return float(x)  # graftlint: disable=retrace
'''
    f = findings_of({"m.py": bare}, [SuppressionRule()])
    assert len(f) == 1
    assert "bare `graftlint: disable=retrace`" in f[0].message
    justified = bare.replace(
        "disable=retrace", "disable=retrace -- fixture reason"
    )
    assert findings_of({"m.py": justified}, [SuppressionRule()]) == []


def test_bare_suppression_still_suppresses_target():
    """Honoring is unchanged — a bare disable silences its rule (the
    hygiene finding gates instead)."""
    from hydragnn_tpu.analysis.rules.suppression import SuppressionRule

    bare = '''
import jax


@jax.jit
def step(x):
    return float(x)  # graftlint: disable=retrace
'''
    f = findings_of({"m.py": bare}, [RetraceRule(), SuppressionRule()])
    assert [x.rule for x in f] == ["suppression"]


def test_bare_disable_all_cannot_silence_the_hygiene_finding():
    """disable=all must not cover the complaint about itself; only an
    explicit justified disable=suppression does."""
    from hydragnn_tpu.analysis.rules.suppression import SuppressionRule

    bare_all = '''
import jax


@jax.jit
def step(x):
    return float(x)  # graftlint: disable=all
'''
    f = findings_of({"m.py": bare_all},
                    [RetraceRule(), SuppressionRule()])
    assert [x.rule for x in f] == ["suppression"]
    excused = bare_all.replace(
        "disable=all",
        "disable=all,suppression -- grandfathered fixture",
    )
    assert findings_of(
        {"m.py": excused}, [RetraceRule(), SuppressionRule()]
    ) == []


def test_bare_suppression_grandfathers_through_baseline(tmp_path):
    """The migration path for pre-existing bare disables: baseline
    them; a SECOND bare disable still gates (count ratchet)."""
    from hydragnn_tpu.analysis.rules.suppression import SuppressionRule

    src_dir = tmp_path / "pkg"
    src_dir.mkdir()
    bad = src_dir / "m.py"
    one = (
        "import jax\n\n\n@jax.jit\ndef step(x):\n"
        "    return float(x)  # graftlint: disable=retrace\n"
    )
    bad.write_text(one)
    baseline = tmp_path / "baseline.json"
    res = run_lint(str(tmp_path), paths=["pkg"],
                   rules=[SuppressionRule()],
                   baseline_path=str(baseline))
    assert len(res.new) == 1
    write_baseline(str(baseline), res.findings)
    res2 = run_lint(str(tmp_path), paths=["pkg"],
                    rules=[SuppressionRule()],
                    baseline_path=str(baseline))
    assert res2.ok and len(res2.baselined) == 1
    bad.write_text(one + (
        "\n\n@jax.jit\ndef step2(y):\n"
        "    return int(y)  # graftlint: disable=retrace\n"
    ))
    res3 = run_lint(str(tmp_path), paths=["pkg"],
                    rules=[SuppressionRule()],
                    baseline_path=str(baseline))
    assert not res3.ok and len(res3.new) == 1


def test_new_family_fingerprints_are_line_stable():
    """New-family findings round-trip the baseline across line moves
    (fingerprints exclude line numbers)."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    f1 = findings_of({"pkg/train/loop.py": SCAN_FMA_FIXTURE},
                     [FpContractRule()])
    shifted = "# moved\n# down\n" + SCAN_FMA_FIXTURE
    f2 = findings_of({"pkg/train/loop.py": shifted}, [FpContractRule()])
    assert len(f1) == len(f2) == 1
    assert f1[0].fingerprint == f2[0].fingerprint
    assert f1[0].line != f2[0].line


def test_cli_explain_prints_seed_registry(capsys):
    cli = _load_cli()
    assert cli.main(["--explain", "hot-coverage"]) == 0
    out = capsys.readouterr().out
    assert "seed registry" in out
    assert "run_training" in out and "ServingEngine" in out
    assert "exemptions:" in out
    assert cli.main(["--explain", "thread-discipline"]) == 0
    out = capsys.readouterr().out
    assert "DynamicBatcher.submit" in out
    assert cli.main(["--explain", "no-such-rule"]) == 2


def test_cli_diff_mode(tmp_path):
    """--diff lints only changed-vs-rev files (restricted view, default
    vocabulary fallback) and refuses --write-baseline."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a clean worktree vs HEAD: nothing (or only this session's
    # already-clean edits) to lint — must exit 0 under --check
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftlint.py"),
         "--diff", "HEAD", "--check"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=240,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # a bad rev is a usage error, never a green no-op
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftlint.py"),
         "--diff", "no-such-rev-xyz", "--check"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=240,
    )
    assert r2.returncode == 2, r2.stdout + r2.stderr
    cli = _load_cli()
    assert cli.main(["--diff", "HEAD", "--write-baseline"]) == 2
    assert cli.main(["--diff", "HEAD", "some/path.py"]) == 2


def test_fp_contract_flags_fused_multiply_subtract():
    """x - a*b contracts into FMS exactly like x + a*b into FMA —
    both signs and both AugAssign forms must flag (review gap)."""
    from hydragnn_tpu.analysis.rules.fp_contract import FpContractRule

    src = '''
import jax


def fold(acc, prods, gs):
    def body(carry, xs):
        lsum, ng = carry
        p, g = xs
        lsum = lsum - p * g
        ng -= p * g
        return (lsum, ng), None

    acc, _ = jax.lax.scan(body, acc, (prods, gs))
    return acc
'''
    f = findings_of({"pkg/train/loop.py": src}, [FpContractRule()])
    assert len(f) == 2, [x.render() for x in f]
    assert all("fusable multiply-add" in x.message for x in f)


def test_thread_discipline_block_true_still_flags():
    """Only an explicit constant block=False is the non-blocking put
    form — block=True (or a variable) must not wave it through."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = NEVER_BLOCK_FIXTURE.replace(
        "self._q.put(row)", "self._q.put(row, block=True)"
    )
    f = findings_of({"pkg/utils/telemetry.py": src},
                    [ThreadDisciplineRule()])
    assert len(f) == 1 and "blocking `.put(...)`" in f[0].message
    ok = NEVER_BLOCK_FIXTURE.replace(
        "self._q.put(row)", "self._q.put(row, block=False)"
    )
    assert findings_of({"pkg/utils/telemetry.py": ok},
                       [ThreadDisciplineRule()]) == []


def test_thread_discipline_from_import_sleep_flags():
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = '''
from time import sleep


def _run_epoch(step_fn, state, loader):
    sleep(0.1)
    return state
'''
    f = findings_of({"pkg/train/loop.py": src}, [ThreadDisciplineRule()])
    assert len(f) == 1 and "time.sleep" in f[0].message


def test_thread_discipline_annassign_thread_is_worker():
    """A type-annotated self._thread: threading.Thread = ... binding
    still marks the class as a persistent worker (review gap)."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        ThreadDisciplineRule,
    )

    src = '''
import threading


class Writer:
    def __init__(self):
        self._thread: threading.Thread = threading.Thread(
            target=self._main, daemon=True
        )
        self._thread.start()

    def _main(self):
        pass

    def close(self):
        pass


def trial(cfg):
    w = Writer()
    w.close()
    return cfg
'''
    f = findings_of({"pkg/utils/writer.py": src},
                    [ThreadDisciplineRule()])
    assert len(f) == 1
    assert "without close()/stop() in a finally" in f[0].message


def test_host_sync_multibranch_driver_and_barrier_path_are_covered():
    """ISSUE 13: the multibranch epoch driver + plan-domain resume
    cursor (MultiBranchLoader.__iter__/skip_to) are host-sync hot
    seeds, and the checkpoint writer's barrier-riding worker path
    (_process_barrier, reached from CheckpointWriter._worker_main via
    the emit chain) is inside the seeded scope — an injected sync in
    either flags; the real files stay clean."""
    from hydragnn_tpu.analysis.engine import collect_files
    from hydragnn_tpu.analysis.callgraph import build_callgraph, seed_scope
    from hydragnn_tpu.analysis.rules.host_sync import (
        HOT_SEEDS,
        HostSyncRule,
    )

    files = [
        "hydragnn_tpu/parallel/multibranch.py",
        "hydragnn_tpu/utils/checkpoint.py",
    ]
    ctx = collect_files(REPO, files)
    graph = build_callgraph(ctx)
    for qual in (
        "MultiBranchLoader.__iter__",
        "MultiBranchLoader.skip_to",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    # the worker's barrier path is reachable from the seeded writer
    scope = seed_scope(graph, HOT_SEEDS)
    assert any(
        q == "_process_barrier" for (_, q) in scope
    ), "_process_barrier not in the host-sync seeded scope"
    assert any(
        q == "_processes_agree_finite" for (_, q) in scope
    ), "_processes_agree_finite not in the host-sync seeded scope"
    f = findings_of(
        {p: pf.text for p, pf in zip(files, ctx.py_files)},
        [HostSyncRule()],
    )
    assert f == [], [x.message for x in f]


def test_host_sync_fleet_emit_paths_are_covered():
    """ISSUE 14: the fleet emit paths — the barrier-row emitter, the
    liveness counters/phase marks (on the feed hot paths), the
    heartbeat builder and its thread — are host-sync hot seeds, so a
    sync smuggled into any of them lints; and the REAL file stays
    clean."""
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(REPO, ["hydragnn_tpu/utils/telemetry.py"])
    graph = build_callgraph(ctx)
    for qual in (
        "bump",
        "note_phase",
        "heartbeat_row",
        "emit_barrier",
        "TelemetryStream._heartbeat_main",
    ):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    # an injected host-sync fixture must flag: a device fetch inside
    # the heartbeat builder (a background thread touching the device
    # would serialize against the training stream)
    bad = (
        "import jax\n"
        "def heartbeat_row(seq, interval_s):\n"
        "    row = {'t': 'heartbeat', 'seq': seq}\n"
        "    row['loss'] = jax.device_get(_LAST_LOSS)\n"
        "    return row\n"
    )
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": bad}, [HostSyncRule()]
    )
    assert any("device_get" in x.message for x in f), [
        x.message for x in f
    ]
    # and one inside the barrier emitter
    bad = (
        "import jax\n"
        "def emit_barrier(site, seq, total_s, barrier_s=None):\n"
        "    jax.block_until_ready(total_s)\n"
        "    return True\n"
    )
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": bad}, [HostSyncRule()]
    )
    assert any("block_until_ready" in x.message for x in f), [
        x.message for x in f
    ]
    # the real file is clean under the expanded seed set
    src = ctx.py_files[0].text
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": src}, [HostSyncRule()]
    )
    assert f == [], [x.message for x in f]


def test_host_sync_barrier_instrumentation_is_covered_and_clean():
    """ISSUE 14: `_process_barrier` / `_processes_agree_finite` are
    now seeded directly (they run on the writer thread AND the
    caller thread at end-of-run) — a jax sync added to the barrier
    timing would fence the training stream and must lint."""
    from hydragnn_tpu.analysis.callgraph import build_callgraph
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    ctx = collect_files(REPO, ["hydragnn_tpu/utils/checkpoint.py"])
    graph = build_callgraph(ctx)
    for qual in ("_process_barrier", "_processes_agree_finite"):
        assert any(
            graph.find(p, q) for p, q in HOT_SEEDS if q == qual
        ), f"{qual} not found among host-sync hot seeds"
    bad = (
        "import jax\n"
        "def _process_barrier(tag, seq=None):\n"
        "    jax.block_until_ready(tag)\n"
    )
    f = findings_of(
        {"hydragnn_tpu/utils/checkpoint.py": bad}, [HostSyncRule()]
    )
    assert any("block_until_ready" in x.message for x in f), [
        x.message for x in f
    ]


def test_thread_discipline_fleet_emitters_never_block():
    """ISSUE 14: emit_barrier/bump/note_phase are never-block seeds —
    a blocking `q.put` (or a sleep) added to the barrier-row path
    would stall the checkpoint worker behind telemetry, and must
    lint."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        NEVER_BLOCK_SEEDS,
        ThreadDisciplineRule,
    )

    for qual in ("emit_barrier", "bump", "note_phase"):
        assert any(
            q == qual for _, q in NEVER_BLOCK_SEEDS
        ), f"{qual} not found among never-block seeds"
    bad = (
        "def emit_barrier(site, seq, total_s, barrier_s=None):\n"
        "    _Q.put({'t': 'barrier', 'site': site})\n"
        "    return True\n"
    )
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": bad},
        [ThreadDisciplineRule()],
    )
    assert any("put" in x.message for x in f), [x.message for x in f]
    # the real module stays clean (put_nowait discipline throughout)
    ctx = collect_files(REPO, ["hydragnn_tpu/utils/telemetry.py"])
    f = findings_of(
        {"hydragnn_tpu/utils/telemetry.py": ctx.py_files[0].text},
        [ThreadDisciplineRule()],
    )
    assert f == [], [x.message for x in f]


def test_config_schema_vocabulary_covers_fleet_keys():
    """The heartbeat_interval_s key (ISSUE 14) must be legal config
    vocabulary, harvested from the real reader
    (utils/telemetry.telemetry_settings)."""
    from hydragnn_tpu.analysis.rules.config_schema import (
        harvest_accepted_keys,
    )

    ctx = collect_files(REPO, ["hydragnn_tpu/utils/telemetry.py"])
    keys = harvest_accepted_keys(ctx)
    assert "heartbeat_interval_s" in keys
    cfg = json.dumps({
        "NeuralNetwork": {
            "Training": {
                "Telemetry": {
                    "enabled": True,
                    "heartbeat_interval_s": 0.5,
                }
            }
        }
    })
    reader = open(
        os.path.join(REPO, "hydragnn_tpu/utils/telemetry.py")
    ).read()
    f = findings_of(
        {
            "hydragnn_tpu/utils/telemetry.py": reader,
            "hydragnn_tpu/config/reader_stub.py": (
                'def read(c):\n'
                '    t = c["NeuralNetwork"]["Training"]\n'
                '    return t.get("Telemetry", {})\n'
            ),
            "examples/fleet/fleet.json": cfg,
        },
        [ConfigSchemaRule()],
    )
    assert f == [], [x.message for x in f]

# ---------------------------------------------------------------------------
# ISSUE 17 — lock-order


ABBA_FIXTURE = '''
import threading


class Pipeline:
    def __init__(self):
        self._head = threading.Lock()
        self._tail = threading.Lock()
        threading.Thread(target=self._fill).start()
        threading.Thread(target=self._drain).start()

    def _fill(self):
        with self._head:
            with self._tail:
                pass

    def _drain(self):
        with self._tail:
            with self._head:
                pass
'''


def test_lock_order_flags_abba_cycle():
    """Two worker threads taking the same pair of locks in opposite
    orders is an ABBA deadlock; the thread entries are DISCOVERED from
    the Thread(target=...) ctors, not registered seeds."""
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    f = findings_of({"pkg/serve/pipe.py": ABBA_FIXTURE},
                    [LockOrderRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "lock-order cycle" in f[0].message
    assert "ABBA" in f[0].message
    assert "Pipeline._head" in f[0].message
    assert "Pipeline._tail" in f[0].message


def test_lock_order_single_lock_shape_is_clean():
    """The rollover shape the serving tier actually uses — submit and
    swap serialized on the SAME handle lock, no second acquisition
    under it — must produce NO order edges and no findings."""
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    src = '''
import threading


class Handle:
    def __init__(self):
        self._lock = threading.Lock()
        self.engine = None
        threading.Thread(target=self._pump).start()

    def _pump(self):
        with self._lock:
            e = self.engine
        e.step()

    def swap(self, eng):
        with self._lock:
            self.engine = eng
'''
    assert findings_of({"pkg/serve/handle.py": src},
                       [LockOrderRule()]) == []


def test_lock_order_cross_function_edge_makes_cycle():
    """Held sets propagate through resolvable call edges: the cycle
    exists even though no single function takes both locks."""
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    src = '''
import threading


class Pipeline:
    def __init__(self):
        self._head = threading.Lock()
        self._tail = threading.Lock()
        threading.Thread(target=self._fill).start()
        threading.Thread(target=self._drain).start()

    def _fill(self):
        with self._head:
            self._append()

    def _append(self):
        with self._tail:
            pass

    def _drain(self):
        with self._tail:
            self._pop()

    def _pop(self):
        with self._head:
            pass
'''
    f = findings_of({"pkg/serve/pipe.py": src}, [LockOrderRule()])
    assert any("lock-order cycle" in x.message for x in f), [
        x.render() for x in f
    ]


def test_lock_order_blocking_under_lock_and_condition_carveout():
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    src = '''
import queue
import threading
import time


class Feeder:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._q = queue.Queue(maxsize=2)
        threading.Thread(target=self._main).start()

    def _main(self):
        with self._lock:
            self._q.put(1)
            time.sleep(0.1)
        with self._lock:
            self._q.put_nowait(2)
            self._q.put(3, block=False)
        with self._cv:
            self._cv.wait()
        with self._lock:
            ev = threading.Event()
            ev.wait()
'''
    f = findings_of({"pkg/serve/feeder.py": src}, [LockOrderRule()])
    msgs = sorted(x.message for x in f)
    assert len(f) == 3, [x.render() for x in f]
    assert any("blocking `.put(...)`" in m for m in msgs)
    assert any("time.sleep" in m for m in msgs)
    # cv.wait() on the HELD Condition releases the lock (the protocol)
    # and is NOT among the findings; ev.wait() on a foreign object is.
    assert any("foreign object" in m for m in msgs)
    assert all("Feeder._cv`" not in m or "foreign" in m for m in msgs)


def test_lock_order_injected_fault_gates_only_when_enabled():
    """Acceptance: the ABBA fixture flags with lock-order enabled and
    stays silent under the OTHER new families (cross-family
    independence)."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    srcs = {"pkg/serve/pipe.py": ABBA_FIXTURE}
    assert findings_of(srcs, [LockOrderRule()]) != []
    assert findings_of(
        srcs, [GuardedFieldRule(), BarrierDisciplineRule()]
    ) == []


# ---------------------------------------------------------------------------
# ISSUE 17 — guarded-field


GUARDED_FIXTURE = '''
import threading


class Handle:
    def __init__(self):
        self._lock = threading.Lock()
        self.engine = None
        self.beat = 0.0
        threading.Thread(target=self._pump).start()

    def swap(self, eng):
        with self._lock:
            self.engine = eng

    def _pump(self):
        e = self.engine
        self.beat = 1.0

    def qsize(self):
        with self._lock:
            e = self.engine
        return e
'''


def test_guarded_field_flags_unlocked_read():
    """`engine` is written under `_lock` in swap(), so the lock-free
    read from the pump thread races the swap; `beat` is NEVER accessed
    under the lock (a deliberate benign race) and stays unflagged."""
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule

    f = findings_of({"pkg/serve/handle.py": GUARDED_FIXTURE},
                    [GuardedFieldRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "unlocked read of `self.engine`" in f[0].message
    assert "Handle._pump" in f[0].message
    assert "snapshot it under the lock" in f[0].message


def test_guarded_field_sanctions_init_assignment_and_held_helper():
    """Negatives: single-assignment-before-thread-start (`_q` bound in
    __init__ only) and the private-helper escape (`_flush` called only
    with `_lock` held inherits the critical section)."""
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule

    src = '''
import queue
import threading


class Writer:
    def __init__(self):
        self._lock = threading.Lock()
        self._q = queue.Queue()
        self._count = 0
        threading.Thread(target=self._main).start()

    def _main(self):
        with self._lock:
            self._q.put_nowait(1)
            self._count = self._count + 1
            self._flush()

    def emit(self):
        self._q.put_nowait(3)

    def _flush(self):
        self._count = 0
'''
    f = findings_of({"pkg/serve/writer.py": src}, [GuardedFieldRule()])
    assert f == [], [x.render() for x in f]


def test_guarded_field_unexposed_class_is_clean():
    """A class with a lock but NO thread exposure (no spawn, not in
    the thread scope) is single-threaded as far as the linted tree
    can tell — no findings."""
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule

    src = '''
import threading


class Cold:
    def __init__(self):
        self._lock = threading.Lock()
        self.x = 0

    def locked(self):
        with self._lock:
            self.x = 1

    def unlocked(self):
        return self.x
'''
    assert findings_of({"pkg/util/cold.py": src},
                       [GuardedFieldRule()]) == []


def test_guarded_field_injected_fault_gates_only_when_enabled():
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    srcs = {"pkg/serve/handle.py": GUARDED_FIXTURE}
    assert findings_of(srcs, [GuardedFieldRule()]) != []
    assert findings_of(
        srcs, [LockOrderRule(), BarrierDisciplineRule()]
    ) == []


# ---------------------------------------------------------------------------
# ISSUE 17 — barrier-discipline


# The PR-13 wedge, verbatim shape: a barrier name minted from the
# call-site counter instead of the writer's enqueue-time sequence.
WEDGE_FIXTURE = '''
from hydragnn_tpu.utils.checkpoint import _barrier_seq


def publish(client, tag):
    seq = _barrier_seq(f"b:{tag}")
    name = f"hgtpu_save:{tag}:{seq}"
    client.wait_at_barrier(name)


def publish_ok(client, tag, job_seq):
    client.wait_at_barrier(f"hgtpu_save:{tag}:{job_seq}")
'''


def test_barrier_discipline_flags_counter_minted_name():
    """The PR-13 shape verbatim: `_barrier_seq` at the call site
    flags AT THE MINT LINE; the enqueue-time-parameter shape is the
    sanctioned idiom and stays clean."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )

    f = findings_of({"pkg/utils/publish.py": WEDGE_FIXTURE},
                    [BarrierDisciplineRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "_barrier_seq(...)" in f[0].message
    assert "PR-13 wedge class" in f[0].message
    assert "enqueue-time" in f[0].message
    # anchored at the mint, not the wait
    assert f[0].line == WEDGE_FIXTURE.splitlines().index(
        '    seq = _barrier_seq(f"b:{tag}")'
    ) + 1


def test_barrier_discipline_flags_time_and_next_mints():
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )

    src = '''
import itertools
import time

_COUNTER = itertools.count()


def settle(client):
    n = f"walltime:{time.time()}"
    client.key_value_set(n, "1")
    client.wait_at_barrier(f"gen:{next(_COUNTER)}")
'''
    f = findings_of({"pkg/utils/settle.py": src},
                    [BarrierDisciplineRule()])
    labels = sorted(x.message for x in f)
    assert len(f) == 2, [x.render() for x in f]
    assert any("time.time()" in m for m in labels)
    assert any("next(...)" in m for m in labels)


def test_barrier_discipline_flags_seqless_process_barrier():
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )

    src = '''
def finalize(barrier):
    _process_barrier("final")


def finalize_ok(job_seq):
    _process_barrier("final", seq=job_seq)
'''
    f = findings_of({"pkg/runner2.py": src}, [BarrierDisciplineRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "without `seq=`" in f[0].message
    assert "finalize" in f[0].message


def test_barrier_discipline_conditional_rendezvous():
    """A barrier WAIT under a process_index test flags; asymmetric KV
    set under the same test (the designed O(P) aggregation) and waits
    under uniform process_count tests do not."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )

    src = '''
import jax


def publish(client, name):
    if jax.process_index() == 0:
        client.wait_at_barrier(name)


def agree(client, name, payload):
    if jax.process_index() == 0:
        client.key_value_set(name, payload)
    if jax.process_count() > 1:
        client.wait_at_barrier(name)
'''
    f = findings_of({"pkg/utils/agree.py": src},
                    [BarrierDisciplineRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "under a `process_index` test" in f[0].message
    assert "publish" in f[0].message


def test_barrier_discipline_collective_on_coord_path_only():
    """sync_global_devices on a coordination path flags (it queues
    device work behind the step stream); the same collective in compute code
    NOT reachable from any coordination site is out of scope."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )

    src = '''
from jax.experimental import multihost_utils


def settle(client, name):
    multihost_utils.sync_global_devices(name)
    client.key_value_set(name, "done")


def gather_metrics(x):
    return multihost_utils.process_allgather(x)
'''
    f = findings_of({"pkg/utils/settle.py": src},
                    [BarrierDisciplineRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "sync_global_devices" in f[0].message
    assert "settle" in f[0].message


def test_barrier_discipline_injected_fault_gates_only_when_enabled():
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    srcs = {"pkg/utils/publish.py": WEDGE_FIXTURE}
    assert findings_of(srcs, [BarrierDisciplineRule()]) != []
    assert findings_of(
        srcs, [LockOrderRule(), GuardedFieldRule()]
    ) == []


# ---------------------------------------------------------------------------
# ISSUE 17 — baseline/fingerprint mechanics for the new families


def test_concurrency_family_fingerprints_are_line_stable():
    """Findings from all three new families keep their fingerprints
    when the file shifts (fingerprints exclude line numbers)."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    for rel, fixture, rule in (
        ("pkg/serve/pipe.py", ABBA_FIXTURE, LockOrderRule()),
        ("pkg/serve/handle.py", GUARDED_FIXTURE, GuardedFieldRule()),
        ("pkg/utils/publish.py", WEDGE_FIXTURE, BarrierDisciplineRule()),
    ):
        f1 = findings_of({rel: fixture}, [rule])
        f2 = findings_of({rel: "# moved\n# down\n" + fixture}, [rule])
        assert len(f1) == len(f2) == 1, (rule.name, f1, f2)
        assert f1[0].fingerprint == f2[0].fingerprint
        assert f1[0].line != f2[0].line


def test_concurrency_family_baseline_grandfather(tmp_path):
    """A pre-existing wedge grandfathers through the baseline; a
    SECOND mint site still gates (count ratchet applies to the new
    families like any other)."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )

    src_dir = tmp_path / "pkg"
    src_dir.mkdir()
    bad = src_dir / "m.py"
    bad.write_text(WEDGE_FIXTURE)
    baseline = tmp_path / "baseline.json"
    res = run_lint(str(tmp_path), paths=["pkg"],
                   rules=[BarrierDisciplineRule()],
                   baseline_path=str(baseline))
    assert not res.ok and len(res.new) == 1
    write_baseline(str(baseline), res.findings)
    res2 = run_lint(str(tmp_path), paths=["pkg"],
                    rules=[BarrierDisciplineRule()],
                    baseline_path=str(baseline))
    assert res2.ok and len(res2.baselined) == 1
    bad.write_text(WEDGE_FIXTURE + (
        "\n\ndef publish_two(client, tag):\n"
        "    client.wait_at_barrier(f\"again:{_barrier_seq(tag)}\")\n"
    ))
    res3 = run_lint(str(tmp_path), paths=["pkg"],
                    rules=[BarrierDisciplineRule()],
                    baseline_path=str(baseline))
    assert not res3.ok and len(res3.new) == 1


def test_suppression_silences_new_families_with_reason():
    """The in-place `disable-next-line=RULE -- why` grammar covers the
    new families (the triage mechanism the real tree uses)."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )
    from hydragnn_tpu.analysis.rules.suppression import SuppressionRule

    src = WEDGE_FIXTURE.replace(
        '    seq = _barrier_seq(f"b:{tag}")',
        "    # graftlint: disable-next-line=barrier-discipline"
        " -- symmetric smoke path\n"
        '    seq = _barrier_seq(f"b:{tag}")',
    )
    f = findings_of({"pkg/utils/publish.py": src},
                    [BarrierDisciplineRule(), SuppressionRule()])
    assert f == [], [x.render() for x in f]


# ---------------------------------------------------------------------------
# ISSUE 17 — real-tree proofs and seed registry (fleet surfaces)


def test_lock_order_real_fleet_rollover_shape_is_safe():
    """The ISSUE-17 proof obligation: the REAL serving tier — replica
    pumps, beat threads, swap/submit on `ReplicaHandle._lock`, the
    tier monitor — has NO lock-order findings (no ABBA cycle, no
    blocking call under a held lock)."""
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    srcs = {}
    for rel in (
        "hydragnn_tpu/serve/fleet.py",
        "hydragnn_tpu/serve/router.py",
        "hydragnn_tpu/serve/batcher.py",
        "hydragnn_tpu/serve/engine.py",
    ):
        path = os.path.join(REPO, rel)
        if os.path.exists(path):
            srcs[rel] = open(path).read()
    assert "hydragnn_tpu/serve/fleet.py" in srcs
    f = findings_of(srcs, [LockOrderRule()])
    assert f == [], [x.render() for x in f]


def test_guarded_field_real_fleet_gauges_are_clean():
    """The gauge paths read `batcher`/`engine` via snapshot-under-lock
    after the ISSUE-17 fix — the real fleet module must carry no
    guarded-field findings."""
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule

    rel = "hydragnn_tpu/serve/fleet.py"
    src = open(os.path.join(REPO, rel)).read()
    f = findings_of({rel: src}, [GuardedFieldRule()])
    assert f == [], [x.render() for x in f]


def test_guarded_field_catches_reintroduced_gauge_race():
    """Seed-registry load test: stripping the snapshot-under-lock from
    a gauge reintroduces the exact race this PR fixed — and the rule
    catches it on the REAL class shape."""
    from hydragnn_tpu.analysis.rules.guarded_field import GuardedFieldRule

    bad = '''
import threading


class ReplicaHandle:
    def __init__(self):
        self._lock = threading.Lock()
        self.batcher = None
        threading.Thread(target=self._pump_main).start()

    def _pump_main(self):
        with self._lock:
            b = self.batcher
        b.drain()

    def swap(self, batcher):
        with self._lock:
            self.batcher = batcher

    def qsize(self):
        return self.batcher.qsize()
'''
    f = findings_of({"hydragnn_tpu/serve/fleet.py": bad},
                    [GuardedFieldRule()])
    assert len(f) == 1, [x.render() for x in f]
    assert "unlocked read of `self.batcher`" in f[0].message
    assert "qsize" in f[0].message


def test_thread_discipline_fleet_kill_paths_are_seeded():
    """ISSUE 17 satellite: ReplicaHandle.kill / ServingTier.kill_replica
    are never-block seeds — a blocking join/sleep smuggled into the
    kill path stalls rollover; and the REAL module stays clean."""
    from hydragnn_tpu.analysis.rules.thread_discipline import (
        NEVER_BLOCK_SEEDS,
        ThreadDisciplineRule,
    )

    for qual in ("ReplicaHandle.kill", "ServingTier.kill_replica"):
        assert any(
            q == qual for p, q in NEVER_BLOCK_SEEDS
            if p == "serve/fleet.py"
        ), f"{qual} not found among never-block seeds"
    bad = (
        "import time\n"
        "class ReplicaHandle:\n"
        "    def kill(self):\n"
        "        time.sleep(1.0)\n"
    )
    f = findings_of(
        {"hydragnn_tpu/serve/fleet.py": bad}, [ThreadDisciplineRule()]
    )
    assert any("time.sleep" in x.message for x in f), [
        x.message for x in f
    ]
    real = open(
        os.path.join(REPO, "hydragnn_tpu/serve/fleet.py")
    ).read()
    f = findings_of(
        {"hydragnn_tpu/serve/fleet.py": real}, [ThreadDisciplineRule()]
    )
    assert f == [], [x.message for x in f]


def test_host_sync_fleet_router_and_pump_paths_are_seeded():
    """ISSUE 17 satellite: the router hot path and the replica
    pump/beat/kill mains are host-sync hot seeds — a device fence in
    the beat thread is a liveness hazard (a wedged device marks every
    replica dead)."""
    from hydragnn_tpu.analysis.rules.host_sync import HOT_SEEDS

    for rel, qual in (
        ("serve/router.py", "Router._route"),
        ("serve/router.py", "Router._shed"),
        ("serve/fleet.py", "ReplicaHandle._pump_main"),
        ("serve/fleet.py", "ReplicaHandle._beat_main"),
        ("serve/fleet.py", "ReplicaHandle.kill"),
        ("serve/fleet.py", "ServingTier.kill_replica"),
    ):
        assert (rel, qual) in HOT_SEEDS, f"{qual} not a hot seed"
    bad = (
        "import jax\n"
        "class ReplicaHandle:\n"
        "    def _beat_main(self):\n"
        "        jax.block_until_ready(self._last)\n"
    )
    f = findings_of(
        {"hydragnn_tpu/serve/fleet.py": bad}, [HostSyncRule()]
    )
    assert any("block_until_ready" in x.message for x in f), [
        x.message for x in f
    ]
    bad = (
        "import jax\n"
        "class Router:\n"
        "    def _route(self, req):\n"
        "        return jax.device_get(req)\n"
    )
    f = findings_of(
        {"hydragnn_tpu/serve/router.py": bad}, [HostSyncRule()]
    )
    assert any("device_get" in x.message for x in f), [
        x.message for x in f
    ]


# ---------------------------------------------------------------------------
# ISSUE 17 — per-rule stats


def test_per_rule_stats_buckets(tmp_path):
    """LintResult.per_rule counts new/baselined/suppressed per family
    (the --stats table and the JSON payload both read it)."""
    from hydragnn_tpu.analysis.rules.barrier_discipline import (
        BarrierDisciplineRule,
    )
    from hydragnn_tpu.analysis.rules.lock_order import LockOrderRule

    src_dir = tmp_path / "pkg"
    src_dir.mkdir()
    (src_dir / "m.py").write_text(WEDGE_FIXTURE)
    res = run_lint(str(tmp_path), paths=["pkg"],
                   rules=[BarrierDisciplineRule(), LockOrderRule()],
                   baseline_path=None)
    assert res.per_rule["barrier-discipline"] == {
        "new": 1, "baselined": 0, "suppressed": 0,
    }
    assert res.per_rule["lock-order"] == {
        "new": 0, "baselined": 0, "suppressed": 0,
    }


def test_cli_stats_table_and_json_per_rule(tmp_path, capsys):
    cli = _load_cli()
    bad = tmp_path / "m.py"
    bad.write_text(WEDGE_FIXTURE)
    rc = cli.main([str(bad), "--stats", "--baseline", ""])
    out = capsys.readouterr().out
    assert rc == 0  # informational mode
    assert "barrier-discipline" in out
    assert "baselined" in out and "suppressed" in out
    assert "total" in out
    rc = cli.main([str(bad), "--json", "--baseline", ""])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    per_rule = payload["per_rule"]
    for fam in ("lock-order", "guarded-field", "barrier-discipline"):
        assert fam in per_rule
    assert per_rule["barrier-discipline"]["new"] == 1
