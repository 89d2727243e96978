"""Binding of the ``op_loop`` driver to the program's row conversion
**on the library's default engines**: the round trip of
``rowconv_roundtrip.py``, which a program may only serve on the engines
the deployment's configuration names (``assumed.engines`` of
``configs/rowconv-upstream-212col-1m.json``, by the backend JAX
reports).

Which engine served a conversion is read from the program, not assumed:
its counter ``srt_row_conversion_total{direction,engine}``.  A program
without the counter serves a fixed-width batch by byte gather, which at
this deployment's 2^20 rows takes half a minute a round trip and runs
out of device memory with three batches alive (PERF.md, Findings,
PR 32), so ``build`` refuses it before it makes any array: the cell
then fails at once and cleanly, and is neither measured at a speed no
user would accept nor killed for memory.  After the warm round trip,
and over the whole window when the outputs are read, every conversion
counted since ``build`` has to be on the configured engine; one on
``gather`` (or on any other) raises.  No option, no variable."""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(os.path.dirname(HERE), "configs",
                      "rowconv-upstream-212col-1m.json")
COUNTER = "srt_row_conversion_total"


def _sibling(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_ops_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_roundtrip = _sibling("rowconv_roundtrip")
rows_per_op = _roundtrip.rows_per_op


def conversions():
    """{(direction, engine): count} as the program's counter reads now;
    ``None`` on a program that has no such counter."""
    from spark_rapids_tpu import observability as obs

    family = obs.METRICS.family_snapshot(COUNTER)
    if family is None:
        return None
    return {tuple(s["labels"]): s["value"] for s in family["series"]}


def check_engines(state, when):
    """Every conversion counted since ``build`` ran on the engine the
    configuration names for its direction, and each direction ran."""
    now = conversions() or {}
    since = {k: v - state["conversions_at_build"].get(k, 0)
             for k, v in now.items()}
    ran = {k: v for k, v in since.items() if v}
    want = state["engines"]
    wrong = {"%s on %s" % k: v for k, v in ran.items()
             if want.get(k[0]) != k[1]}
    if wrong:
        raise RuntimeError(
            "%s the program's %s counted %s; the configuration names "
            "the engines %s" % (when, COUNTER,
                                json.dumps(wrong, sort_keys=True),
                                json.dumps(want, sort_keys=True)))
    idle = sorted(d for d in want if not any(k[0] == d for k in ran))
    if idle:
        raise RuntimeError(
            "%s the program's %s counted no conversion %s: which engine "
            "ran cannot be shown" % (when, COUNTER, " nor ".join(idle)))


def build(inputs):
    at_build = conversions()
    if at_build is None:
        raise RuntimeError(
            "no engine counter (%s): this program serves a fixed-width "
            "batch by byte gather; 29.8 s a round trip and "
            "RESOURCE_EXHAUSTED at 2^20 rows, PERF.md" % COUNTER)
    import jax

    with open(CONFIG) as f:
        engines = json.load(f)["assumed"]["engines"]
    backend = jax.default_backend()
    if backend not in engines:
        raise RuntimeError("the configuration names no engines for the "
                           "backend %r (%s)" % (backend, CONFIG))
    state = _roundtrip.build(inputs)
    state.update(conversions_at_build=at_build, engines=engines[backend],
                 warmed=False)
    return state


def run(state, annotate):
    out = _roundtrip.run(state, annotate)
    if not state["warmed"]:     # the driver's first call warms the shapes
        check_engines(state, "after the warm round trip")
        state["warmed"] = True
    return out


def produced(state, outputs):
    check_engines(state, "over the window")
    return _roundtrip.produced(state, outputs)
