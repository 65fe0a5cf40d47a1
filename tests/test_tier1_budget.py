"""Tier-1 wall-clock budget: the heavy-soak `slow` marks must not regress.

The tier-1 suite runs under a hard timeout (`-m 'not slow'`); the tests
below were measured as the dominant non-headline soaks and deliberately
moved behind the `slow` marker so the budget fits. A refactor that renames
or re-inlines one of them silently re-inflates the suite past its timeout —
so this meta-test pins the decision by NAME, via AST only (no imports, no
fixtures, milliseconds).

When one of these genuinely gets fast (or is deleted), update the list —
that's the point: the budget change becomes an explicit diff, not an
accident.
"""

import ast
import os

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))

# (file, test function name) — every entry must carry @pytest.mark.slow.
# Keep the per-subsystem HEADLINE e2es out of this list: they stay tier-1.
SLOW_SOAKS = [
    ("test_sampling.py", "test_greedy_row"),
    ("test_serve_dataplane.py",
     "test_loadtest_affinity_preemption_and_drained_scale_down"),
    ("test_serve_fleet.py", "test_replica_crash_is_not_client_visible"),
    ("test_recorder.py", "test_scaled_lane_reports_recorder_on"),
    ("test_pool_queue.py",
     "test_cross_queue_reclaim_evicts_borrower_end_to_end"),
    ("test_train.py", "test_interrupted_run_equals_uninterrupted"),
    ("test_train.py", "test_restore_onto_smaller_mesh_keeps_training"),
    ("test_serve.py", "test_high_priority_serve_preempts_training"),
    ("test_sched.py", "test_elastic_victim_sheds_workers_instead_of_dying"),
    ("test_input_pipeline.py",
     "test_synthetic_loss_trajectory_is_bit_identical"),
    ("test_input_pipeline.py", "test_loader_loss_trajectory_is_bit_identical"),
    ("test_elastic.py", "test_grow_promotes_a_parked_spare"),
    ("test_models.py", "test_train_step"),
    ("test_parallel.py", "test_fused_kernel_matches_xla_ragged"),
    ("test_train.py", "test_run_lm_training_with_stage_axis"),
    ("test_models.py", "test_grad_accumulation_matches_full_batch"),
    ("test_models.py", "test_loss_decreases"),
    ("test_models.py", "test_mlm_loss_and_convergence"),
    ("test_serving.py", "test_more_requests_than_slots"),
    ("test_generate.py", "test_incremental_decode_matches_full_forward"),
    ("test_cbench.py", "test_probe_at_100k_apps_names_the_next_wall"),
]


def _has_slow_mark(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        # @pytest.mark.slow (possibly called: @pytest.mark.slow())
        node = dec.func if isinstance(dec, ast.Call) else dec
        if (isinstance(node, ast.Attribute) and node.attr == "slow"
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "mark"):
            return True
    return False


def _functions(tree: ast.Module):
    for top in tree.body:
        if isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield top
        elif isinstance(top, ast.ClassDef):
            for item in top.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield item


def test_known_heavy_soaks_stay_behind_the_slow_marker():
    trees = {}
    missing, unmarked = [], []
    for fname, test in SLOW_SOAKS:
        if fname not in trees:
            with open(os.path.join(TESTS_DIR, fname)) as f:
                trees[fname] = ast.parse(f.read(), filename=fname)
        fns = [fn for fn in _functions(trees[fname]) if fn.name == test]
        if not fns:
            missing.append(f"{fname}::{test}")
        elif not any(_has_slow_mark(fn) for fn in fns):
            unmarked.append(f"{fname}::{test}")
    assert not missing, (
        f"budget list is stale — tests gone or renamed: {missing}; "
        "update SLOW_SOAKS to match (and keep the replacement marked slow)")
    assert not unmarked, (
        f"heavy soaks lost their @pytest.mark.slow: {unmarked}; "
        "tier-1 runs under a hard timeout — re-mark them (or, if one "
        "genuinely got fast, remove it from SLOW_SOAKS explicitly)")


def test_slow_marker_is_registered():
    # an unregistered marker dies under --strict-markers and silently
    # matches nothing under -m: pin its registration
    with open(os.path.join(TESTS_DIR, os.pardir, "pyproject.toml")) as f:
        doc = f.read()
    markers = doc.split("markers = [", 1)[1].split("]", 1)[0]
    assert '"slow:' in markers

