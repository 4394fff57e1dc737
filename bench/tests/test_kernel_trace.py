"""The program's Pallas densify kernel (``kernels/jagged``) run under
``jax.named_scope("densify")`` in a small jitted function and traced on one
TPU v5e (``record_jagged_trace.py``): the kernel's custom call carries the
scope in its op name, so the reduction counts its device time under the
scope, and ``bench.roofline`` reads its share from that time."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import roofline, spans, trace
from bench.tests import record_jagged_trace as rec

FIXTURES = Path(__file__).parent / "fixtures"


def _load():
    t = json.loads(gzip.decompress(
        (FIXTURES / "jagged_trace.json.gz").read_bytes()))
    hlo = gzip.decompress((FIXTURES / "jagged_hlo.txt.gz").read_bytes())
    return t, hlo.decode()


def test_kernel_is_counted_under_its_scope():
    t, hlo = _load()
    assert spans.op_scopes(hlo, spans.DEFAULT_SCOPES + (rec.SCOPE,)) \
        == t["scopes"]
    (module, scopes), = t["scopes"].items()
    kernels = [ln.split(" = ")[0].strip().lstrip("%")
               for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(scopes[k] == rec.SCOPE for k in kernels)
    device = [ev for p in t["planes"] if trace.DEVICE_PLANE.match(p["name"])
              for ln in p["lines"] for ev in ln["events"]]
    ran = [ev for ev in device
           if ev[0].split(" ")[0] in kernels]
    assert len(ran) >= rec.STEPS

    s = spans.summarize(t)
    assert s["steps"] == rec.STEPS
    by_scope = s["device_by_scope"]
    assert sum(by_scope.values()) == pytest.approx(s["busy_s"], rel=1e-9)
    # the kernel's own events, all inside the scope's time
    assert by_scope[rec.SCOPE] * 1e9 >= sum(d for _, _, d in ran[:rec.STEPS])
    # the layout copy of the values before it carries no scope
    assert by_scope.get(spans.UNSCOPED, 0) > 0


def test_roofline_share_of_the_kernel():
    t, hlo = _load()
    # the values the record made are the kernel's operand in the HLO
    assert f"values.1: f32[{rec.lengths().sum()},{rec.DIM}]" in hlo
    # the compiler placed the kernel's operand and result in VMEM (memory
    # space 1); the cost counts their bytes all the same
    call, = [ln for ln in hlo.splitlines() if "custom-call(" in ln]
    operand = call.split("custom-call(%copy-done, %")[1].split(")")[0]
    defined, = [ln for ln in hlo.splitlines()
                if ln.strip().startswith(f"%{operand} = ")]
    assert "S(1)}" in defined.split(" = ")[1].split(" ")[0]
    assert "S(1)}" in call.split(" = ")[1].split(" ")[0]
    flops, nbytes = rec.cost()
    assert nbytes == (35106 * 64 + 256 * 200 * 64) * 4 + 257 * 4
    w = SimpleNamespace(
        kernel_cost={rec.SCOPE: (flops * rec.STEPS, nbytes * rec.STEPS)},
        trace=spans.summarize(t), chips=1, device_kind="TPU v5 lite")
    share = roofline.share(w, rec.SCOPE)
    assert share.bound == "bytes"
    assert share.percent == pytest.approx(66.066, abs=0.001)
