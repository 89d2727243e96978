"""``lib/scopes.py`` on a hand-made trace shaped as a TPU trace is: a
device plane whose "XLA Ops" events are named by their HLO instruction
alone, an "XLA Modules" line that names the program, a host plane with
the reader's annotations, and xprof's tf_op names by (program,
operation).  No JAX device, no program.

    python3 -m pytest benchmark/tests/test_scopes.py -q
"""

import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lib import scopes  # noqa: E402

MS = 1_000_000


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def _device(shift):
    """One query a bracket: the exchange's sort and all-to-all, the
    probe's sort, in program 77 (and a sort.4 of program 5 that shares
    a name)."""
    ops = []
    for q in range(2):
        t = q * 100 * MS + shift
        ops += [_event("%sort.4 = (s32[8]) sort(s32[8] %p)", t, 10 * MS),
                _event("%all-to-all.1 = s64[8] all-to-all(s64[8] %x)",
                       t + 10 * MS, 5 * MS),
                _event("%sort.9 = (u32[8]) sort(u32[8] %k)", t + 20 * MS,
                       30 * MS)]
    modules = [_event("jit_fn(77)", q * 100 * MS, 90 * MS)
               for q in range(2)]
    return _plane("/device:TPU:%d" % shift, XLA_Modules=modules,
                  XLA_Ops=ops)


def test_scope_seconds_sums_the_scopes_operations_per_query_and_device(
        monkeypatch):
    host = _plane("/host:CPU", python=[
        _event(scopes.BRACKET, q * 100 * MS, 95 * MS) for q in range(2)])
    profile = types.SimpleNamespace(planes=[_device(0), _device(1), host])
    import jax.profiler
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda _path: profile))
    monkeypatch.setattr(scopes, "tf_ops", lambda _path: {
        ("77", "sort.4"): "jit(fn)/srt/map/web_sales.ws_key/sort:",
        ("77", "all-to-all.1"): "jit(fn)/srt/map/web_sales.ws_key/all_to_all:",
        ("77", "sort.9"): "jit(fn)/srt/map/wj.li/sort:",
        ("5", "sort.4"): "jit(other)/srt/map/wj.li/sort:"})
    got, found = scopes.scope_seconds("trace", ["srt/map/web_sales."])
    assert found and got == [[0.015, 0.015], [0.015, 0.015]]
    got, found = scopes.scope_seconds("trace", ["srt/map/wj."])
    assert found and got == [[0.03, 0.03], [0.03, 0.03]]
    assert scopes.scope_seconds("trace", ["srt/map/web_returns."])[1] is False


def test_program_id_of_a_module_event():
    assert scopes._program("jit_body(3537216766759305726)") == (
        "3537216766759305726")
