"""Documentation drift guard (DESIGN.md §13).

The telemetry section promises an EXHAUSTIVE cross-reference: every field of
every ``*Stats`` dataclass in ``src/repro`` maps to a registry series (or is
explicitly called out as not adapter-published), and every directly
registered metric name is documented.  These tests walk the live code — new
counters or metrics added without a DESIGN.md row fail tier-1 instead of
rotting the docs.
"""
import dataclasses
import importlib
import inspect
import pkgutil
import re
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
DESIGN = (REPO_ROOT / "DESIGN.md").read_text()
SRC = REPO_ROOT / "src" / "repro"


def _all_stats_classes():
    import repro

    out = {}
    for mod in pkgutil.walk_packages(repro.__path__, "repro."):
        m = importlib.import_module(mod.name)   # import errors ARE failures
        for name, obj in vars(m).items():
            if (inspect.isclass(obj) and dataclasses.is_dataclass(obj)
                    and name.endswith("Stats")
                    and obj.__module__ == m.__name__):
                out[name] = obj
    return out


def test_every_stats_field_documented_in_design():
    classes = _all_stats_classes()
    assert len(classes) >= 13, sorted(classes)   # the §13 inventory
    missing = []
    for cls_name, cls in sorted(classes.items()):
        for f in dataclasses.fields(cls):
            if f"{cls_name}.{f.name}" not in DESIGN:
                missing.append(f"{cls_name}.{f.name}")
    assert not missing, (
        "DESIGN.md §13 cross-reference is missing *Stats fields "
        f"(add a mapping row or a not-published note): {missing}")


# a directly registered metric: counter/gauge/histogram( "repro_..."
# possibly with the name literal on the following line
_METRIC_RE = re.compile(
    r"(?:counter|gauge|histogram)\(\s*\n?\s*\"(repro_[a-z0-9_]+)\"")


def test_every_registered_metric_name_documented_in_design():
    names = set()
    for path in SRC.rglob("*.py"):
        names.update(_METRIC_RE.findall(path.read_text()))
    # the adapter's f-string families are covered by the naming rule + the
    # cross-reference table; this walk catches the directly named metrics
    assert "repro_stage_seconds" in names       # the walk itself works
    assert "repro_store_rtt_seconds" in names
    missing = sorted(n for n in names if n not in DESIGN)
    assert not missing, (
        f"DESIGN.md §13 is missing registered metric names: {missing}")


_EVENT_RE = re.compile(r"""(?:events\.emit|_emit)\(\s*\n?\s*"([a-z_]+)\"""")


def test_every_emitted_event_kind_documented_in_design():
    kinds = set()
    for path in SRC.rglob("*.py"):
        kinds.update(_EVENT_RE.findall(path.read_text()))
    # breaker transitions are emitted via an f-string on the state name
    kinds.update({"breaker_open", "breaker_half_open", "breaker_closed"})
    assert "generation_flip" in kinds and "worker_restart" in kinds
    missing = sorted(k for k in kinds if f"`{k}`" not in DESIGN)
    assert not missing, (
        f"DESIGN.md §13 event-kind list is missing: {missing}")


# a profiler span: stage("<layer>", "<stage>" -> repro.<layer>.<stage>, or an
# annotation named by its literal; a model scope: jax.named_scope("<scope>")
_STAGE_RE = re.compile(r"\bstage\(\s*\"([a-z_]+)\",\s*\"([a-z_]+)\"")
_SPAN_RE = re.compile(
    r"(?:_annotate|Annotation)\(\s*\"(repro\.[a-z_]+\.[a-z_]+)\"")
_SCOPE_RE = re.compile(r"named_scope\(\s*\"([a-z_]+)\"\s*\)")


def test_every_span_and_scope_documented_in_design():
    section = DESIGN[DESIGN.index("## §13"):DESIGN.index("## §14")]
    spans, scopes = set(), set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        spans.update(f"repro.{a}.{b}" for a, b in _STAGE_RE.findall(text))
        spans.update(_SPAN_RE.findall(text))
        scopes.update(_SCOPE_RE.findall(text))
    # the walk itself works
    assert {"repro.dpp.scan", "repro.train.step", "repro.host.gc"} <= spans
    assert {"embed", "encoder", "logits", "optimizer"} <= scopes
    missing = sorted(n for n in spans | scopes if f"| `{n}` |" not in section)
    assert not missing, (
        f"DESIGN.md §13 has no row for these profiler spans or model "
        f"scopes: {missing}")
