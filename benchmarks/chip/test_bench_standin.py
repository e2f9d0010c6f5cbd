"""A second model joins the benchmark by added files alone.

``testdata/standin/`` holds what a later change adds for a model that is
not ResNet: its configuration (the sizes, and in ``.py`` the program, the
data, the work counts in tokens, the test size and the plain reference),
a traffic mix, the limits of its cell, a reader of one of its own
``model.<name>`` scopes, and its entries for ``BENCHMARK.json``
(``entries.json``, with ``train_tokens_per_s``, which no committed cell
reports).  Each test lays those files over a copy of the benchmark's,
points ``catalog`` at the copy, and runs on the stand-in's cell one of
the checks that every committed cell passes, as it stands.
"""
from __future__ import annotations

import inspect
import json
import shutil

import pytest

import catalog
import test_bench_catalog as catalog_checks
import test_bench_faults as fault_checks
import test_bench_trace as trace_checks

STANDIN = catalog.HERE / "testdata" / "standin"
ENTRIES = json.loads((STANDIN / "entries.json").read_text())
(CELL,) = [w["name"] for w in ENTRIES["workloads"]]
(METRIC,) = [m["name"] for m in ENTRIES["per_layer"]]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark's files with the stand-in's added."""
    root = tmp_path_factory.mktemp("standin")
    here = root / "benchmarks" / "chip"
    shutil.copytree(catalog.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    shutil.copytree(STANDIN, here, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("entries.json"))
    bench = catalog.benchmark()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[kind] += ENTRIES.get(kind, [])
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return root


@pytest.fixture
def standin(copy, monkeypatch):
    monkeypatch.setattr(catalog, "ROOT", copy)
    monkeypatch.setattr(catalog, "HERE", copy / "benchmarks" / "chip")


def call(check, **given):
    """``check`` with the arguments it names, from ``given``."""
    return check(**{k: given[k] for k in inspect.signature(check).parameters})


def test_standin_is_not_a_committed_cell():
    assert CELL not in {w["name"] for w in catalog.benchmark()["workloads"]}
    assert not (catalog.HERE / "configs" / "standin-lm.json").exists()


@pytest.mark.parametrize("check", [
    "test_keys_and_limits", "test_names_and_units",
    "test_cell_files_found_by_name", "test_metric_readers",
    "test_layers_share_one_name_each", "test_command_stays_in_paths"])
def test_catalog_checks(check, standin):
    call(getattr(catalog_checks, check), cell=CELL, metric=METRIC)


@pytest.mark.parametrize("check", [
    "test_sound_run_is_correct", "test_state_left_unchanged_is_caught",
    "test_half_batch_is_caught", "test_altered_gossip_answer_is_caught",
    "test_bf16_control_fails_the_limits"])
def test_fault_checks(check, standin, tiny_cell, monkeypatch):
    call(getattr(fault_checks, check), cell=CELL, tiny_cell=tiny_cell,
         monkeypatch=monkeypatch)


def test_untraced_run_reports_tokens_per_s(standin, tiny_cell):
    out = fault_checks.run(CELL, tiny_cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_traced_run_reads_the_models_own_scope(standin, tiny_cell,
                                               monkeypatch):
    out = trace_checks.check_traced_run(CELL, monkeypatch, tiny_cell)
    assert set(out["metrics"]) == {METRIC}
