"""docs/OP_COVERAGE.md is a tested contract, not prose: the primitive
matrix must match the frontend's actual ``eqn_*`` handlers, the real
dispatcher, and the real kind vocabulary — so the docs cannot silently rot
when a lowering rule is added or renamed."""
from __future__ import annotations

import pathlib
import re

from repro.core import frontend, ir, metrics

DOC = pathlib.Path(__file__).resolve().parents[1] / "docs" / "OP_COVERAGE.md"


def _matrix_rows() -> list[list[str]]:
    """Cells of every body row of the '## Primitive matrix' table."""
    text = DOC.read_text()
    section = text.split("## Primitive matrix", 1)[1].split("\n## ", 1)[0]
    rows = []
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if cells[0].startswith("JAX primitive") or set(cells[0]) <= {"-", " "}:
            continue  # header / separator
        rows.append(cells)
    assert rows, "primitive matrix table not found in docs/OP_COVERAGE.md"
    assert all(len(r) == 7 for r in rows), [len(r) for r in rows]
    return rows


def _ticked(cell: str) -> set[str]:
    return set(re.findall(r"`([^`]+)`", cell))


def test_matrix_handlers_match_tracer() -> None:
    documented = set()
    for row in _matrix_rows():
        documented |= _ticked(row[1])
    actual = {m for m in dir(frontend._Tracer) if m.startswith("eqn_")}
    assert documented == actual, (
        f"docs list handlers {sorted(documented)} but _Tracer defines "
        f"{sorted(actual)}"
    )


def test_matrix_primitives_match_dispatcher() -> None:
    dispatched = (
        {"conv_general_dilated", "dot_general", "scan"}
        | set(frontend._REDUCE_WINDOW_PRIMS)
        | set(frontend._SPATIAL_REDUCE_PRIMS)
    )
    documented = set()
    for row in _matrix_rows():
        # The primitive cell may carry qualifiers ("(weight operand)");
        # only the backticked names are primitive claims.
        documented |= {
            p.split(" ")[0] for p in _ticked(row[0]) if not p.startswith("(")
        }
    # Every special-cased primitive is documented, and the docs name no
    # primitive the dispatcher does not special-case.
    assert documented == dispatched, (
        f"docs: {sorted(documented)} vs dispatcher: {sorted(dispatched)}"
    )


def test_matrix_kinds_are_real_and_complete() -> None:
    documented = set()
    for row in _matrix_rows():
        documented |= _ticked(row[3])
    assert documented <= set(ir.KINDS), documented - set(ir.KINDS)
    assert documented == set(ir.KINDS), (
        f"kinds missing from the matrix: {set(ir.KINDS) - documented}"
    )


def test_matrix_support_columns_are_total() -> None:
    # The lock-step tests make support all-or-nothing per kind; the matrix
    # must not claim a partial row that the evaluator cannot distinguish.
    for row in _matrix_rows():
        assert [row[4], row[5], row[6]] == ["yes", "yes", "yes"], row


def test_cost_model_notes_claims() -> None:
    text = DOC.read_text()
    # "13th feature column (metrics.F_STATE)"
    assert "F_STATE" in text
    cols = [getattr(metrics, n) for n in dir(metrics) if n.startswith("F_")]
    assert metrics.F_STATE == max(cols) == 12
    # The builders named in the doc must exist on the frontend.
    for fn in ("transformer_graph", "mamba_graph", "moe_block_graph",
               "vgg16_network", "resnet18_graph", "mobilenet_graph",
               "mlp_block_graph"):
        assert f"`frontend.{fn}`" in text or fn in text
        assert hasattr(frontend, fn), fn


def test_architecture_doc_names_real_paths() -> None:
    arch = DOC.with_name("ARCHITECTURE.md").read_text()
    root = DOC.parents[1]
    for rel in ("bench/run.py", "bench/drivers.py", "benchmarks/run.py",
                "tests/test_multidevice.py", "benchmarks/bench_zoo.py",
                "docs/OP_COVERAGE.md"):
        assert rel.rsplit("/", 1)[-1] in arch, rel
        assert (root / rel).exists(), rel


# ---------------------------------------------------------------------------
# docs/SERVICE.md — the serving/journal/breaker contract
# ---------------------------------------------------------------------------

SERVICE_DOC = DOC.with_name("SERVICE.md")


def _table_rows(section_heading: str) -> list[list[str]]:
    """Body rows of the (single) markdown table under ``section_heading``."""
    text = SERVICE_DOC.read_text()
    section = text.split(section_heading, 1)[1].split("\n## ", 1)[0]
    rows = []
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if set(cells[1]) <= {"-", " "}:
            continue  # separator
        rows.append(cells)
    header, body = rows[0], rows[1:]
    assert body, f"no table under {section_heading!r} in docs/SERVICE.md"
    return body


def test_service_doc_journal_record_table_matches_code() -> None:
    from repro.core import journal

    documented = [_ticked(row[0]).pop() for row in
                  _table_rows("## Journal record format")]
    # exact vocabulary AND order: the doc table is the WAL's contract
    assert documented == list(journal.RECORD_TYPES), (
        f"docs table: {documented} vs journal.RECORD_TYPES: "
        f"{list(journal.RECORD_TYPES)}"
    )


def test_service_doc_breaker_table_matches_enum() -> None:
    from repro.core import service

    documented = {_ticked(row[0]).pop() for row in
                  _table_rows("## Circuit breaker")}
    actual = {s.name for s in service.BreakerState}
    assert documented == actual, (
        f"docs table: {sorted(documented)} vs BreakerState: {sorted(actual)}"
    )


def test_service_doc_lifecycle_names_real_states_and_errors() -> None:
    text = SERVICE_DOC.read_text()
    for state in ("admitted", "queued", "sweeping", "served", "cancelled",
                  "recovered", "rejected", "expired"):
        assert state in text, f"lifecycle state {state!r} missing"
    from repro.core import errors

    for err in ("GraphValidationError", "ConfigValidationError",
                "DeadlineExceeded", "ServiceOverloaded", "RequestCancelled",
                "AuditMismatch", "TransientFailure", "JournalCorrupt"):
        assert err in text, f"typed error {err!r} missing from the doc"
        assert hasattr(errors, err), err


def test_service_doc_names_real_paths_and_knobs() -> None:
    text = SERVICE_DOC.read_text()
    root = SERVICE_DOC.parents[1]
    for rel in ("tests/test_journal.py", "tests/test_journal_property.py",
                "tests/test_docs.py", "examples/serve_lm.py",
                "bench/traffic/serve.json"):
        assert rel in text, rel
        assert (root / rel).exists(), rel
    # every knob the doc mentions is a real constructor parameter
    import inspect

    from repro.core.service import AsyncPlanningService, PlanningService

    params = set(inspect.signature(PlanningService.__init__).parameters)
    params |= set(inspect.signature(AsyncPlanningService.__init__).parameters)
    for knob in ("journal_dir", "hw_chunk", "shadow_audit_rate",
                 "breaker_threshold", "breaker_cooldown_seconds",
                 "watchdog_seconds"):
        assert knob in text, knob
        assert knob in params, knob


# ---------------------------------------------------------------------------
# docs/RESILIENCE.md — quarantine / salvage / resumable-checkpoint contract
# ---------------------------------------------------------------------------

RESILIENCE_DOC = DOC.with_name("RESILIENCE.md")


def _resilience_rows(section_heading: str) -> list[list[str]]:
    """Body rows of the (single) markdown table under ``section_heading``."""
    text = RESILIENCE_DOC.read_text()
    section = text.split(section_heading, 1)[1].split("\n## ", 1)[0]
    rows = []
    for line in section.splitlines():
        line = line.strip()
        if not line.startswith("|"):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if set(cells[1]) <= {"-", " "}:
            continue  # separator
        rows.append(cells)
    header, body = rows[0], rows[1:]
    assert body, f"no table under {section_heading!r} in docs/RESILIENCE.md"
    return body


def test_resilience_doc_taxonomy_verdicts_are_real_errors() -> None:
    from repro.core import errors

    documented = set()
    for row in _resilience_rows("## Fault taxonomy"):
        documented |= {
            name for name in _ticked(row[2]) if name.endswith("Error")
            or name in ("TransientFailure", "JournalCorrupt")
        }
    for name in documented:
        assert hasattr(errors, name), name
        assert issubclass(getattr(errors, name), errors.EvaluatorError), name
    for required in ("PoisonedResultError", "TransientFailure",
                     "JournalCorrupt", "GraphValidationError"):
        assert required in documented, required


def test_resilience_doc_checkpoint_record_table_matches_code() -> None:
    from repro import checkpoint

    documented = [_ticked(row[0]).pop() for row in
                  _resilience_rows("## Checkpoint record types")]
    # exact vocabulary AND order: the doc table is the chunk log's contract
    assert documented == list(checkpoint.SWEEP_RECORD_TYPES), (
        f"docs table: {documented} vs SWEEP_RECORD_TYPES: "
        f"{list(checkpoint.SWEEP_RECORD_TYPES)}"
    )


def test_resilience_doc_retry_knob_table_matches_dataclass() -> None:
    import dataclasses as dc

    from repro.core.errors import RetryPolicy

    rows = _resilience_rows("## Retry policy knobs")
    documented = [_ticked(row[0]).pop() for row in rows]
    fields = {f.name: f for f in dc.fields(RetryPolicy)}
    assert documented == list(fields), (
        f"docs table: {documented} vs RetryPolicy fields: {list(fields)}"
    )
    for row in rows:
        name = _ticked(row[0]).pop()
        assert _ticked(row[1]).pop() == repr(fields[name].default), (
            f"{name}: doc default {row[1]} vs code {fields[name].default!r}"
        )


def test_resilience_doc_injector_knobs_and_hooks_are_real() -> None:
    import inspect

    from repro.testing.faults import FaultInjector

    text = RESILIENCE_DOC.read_text()
    params = set(inspect.signature(FaultInjector.__init__).parameters)
    for knob in ("shard_fail_chunks", "shard_fail_every", "mesh_fail_sweeps",
                 "poison_cell", "poison_value", "transient_sweeps",
                 "chunk_stall_seconds"):
        assert knob in text, knob
        assert knob in params, knob
    for hook in ("before_chunk_compute", "poison_plane"):
        assert hook in text, hook
        assert callable(getattr(FaultInjector, hook)), hook


def test_resilience_doc_names_real_symbols_and_paths() -> None:
    text = RESILIENCE_DOC.read_text()
    root = RESILIENCE_DOC.parents[1]
    for rel in ("tests/test_salvage.py", "tests/test_salvage_property.py",
                "tests/test_faults.py", "tests/test_multidevice.py"):
        assert rel in text, rel
        assert (root / rel).exists(), rel
    from repro.core import flow, metrics
    from repro.core.service import PlanningService
    from repro.runtime import elastic, fault_tolerance

    assert "poison_mask" in text and hasattr(metrics, "poison_mask")
    assert "assert_exact_f64" in text and hasattr(metrics, "assert_exact_f64")
    assert "MAX_EXACT_WORDS" in text and metrics.MAX_EXACT_WORDS == 2.0 ** 53
    assert "sweep_degradation_ladder" in text
    assert callable(elastic.sweep_degradation_ladder)
    assert "StragglerDetector" in text
    assert callable(fault_tolerance.StragglerDetector)
    for field in ("chunks_restored", "chunks_computed", "straggler_chunks",
                  "mesh_degraded", "quarantine"):
        assert field in text, field
        assert field in {f.name for f in __import__("dataclasses").fields(
            flow.FleetResult)}, field
    import inspect

    params = set(inspect.signature(PlanningService.__init__).parameters)
    run_fleet_params = set(inspect.signature(flow.run_fleet).parameters)
    for knob in ("retry_policy", "checkpoint_dir"):
        assert knob in text, knob
        assert knob in params and knob in run_fleet_params, knob
