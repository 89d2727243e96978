"""One caller repeating one operation on data resident on the device.
The operation is a binding under ``benchmark/ops`` named by the traffic
file; the data and the answer come from the traffic's reference."""

import contextlib
import gc
import random
import time

KEEP = 2          # outputs kept for the comparison besides the last
NO_SPAN = lambda _name: contextlib.nullcontext()  # noqa: E731


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.op = cell.load("ops", cell.traffic["op_binding"])
        self.ref = cell.reference
        self.state = None
        self.sample, self.last = [], None   # (op index, outputs)
        self.n_ops = 0
        self.rng = random.Random(cell.seed)

    def setup(self):
        c = self.cell
        inputs = self.ref.make_inputs(c.sizes, c.traffic["params"], c.seed)
        self.state = self.op.build(inputs)
        del inputs
        t0 = time.perf_counter()
        self.op.run(self.state, NO_SPAN)       # warms every shape
        return {"ops_warmed": 1, "warm_op_s": time.perf_counter() - t0}

    def window(self, seconds, annotate=None, min_ops=0, max_ops=None):
        annotate = annotate or NO_SPAN
        records = []
        t0 = time.perf_counter()
        while (max_ops is None or len(records) < max_ops) and (
                len(records) < min_ops
                or time.perf_counter() - t0 < seconds):
            ts = time.perf_counter()
            outputs, spans = self.op.run(self.state, annotate)
            te = time.perf_counter()
            self._keep(outputs)
            records.append({"op": self.cell.traffic["op"], "stream": 0,
                            "t_start": ts, "t_end": te, "ok": True,
                            "rows": self.op.rows_per_op(self.state),
                            "spans": spans, "index": self.n_ops - 1})
        return records

    def _keep(self, outputs):
        """A seeded reservoir of KEEP earlier outputs, and the last."""
        if self.last is not None:
            index = self.last[0]
            if len(self.sample) < KEEP:
                self.sample.append(self.last)
            else:
                j = self.rng.randrange(index + 1)
                if j < KEEP:
                    self.sample[j] = self.last
        self.last = (self.n_ops, outputs)
        self.n_ops += 1

    def probes(self):
        return {}

    def produced(self):
        """Host copies of the kept outputs (read before the state is
        freed, compared after)."""
        kept = self.sample + ([self.last] if self.last else [])
        out = {i: self.op.produced(self.state, o) for i, o in kept}
        self.sample, self.last = [], None
        return out

    def release(self):
        self.state = None
        gc.collect()

    def check(self, records, produced):
        c = self.cell
        inputs = self.ref.make_inputs(c.sizes, c.traffic["params"], c.seed)
        want = self.ref.answer(inputs, c.traffic["params"])
        numbers = {"answers_missing": int(not produced),
                   "answers_compared": len(produced)}
        for got in produced.values():
            for name, v in self.ref.compare(got, want).items():
                numbers[name] = max(numbers.get(name, 0), v)
        return numbers
