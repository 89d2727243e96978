"""Closed-loop query streams against one in-process ``QueryServer``:
each stream is a tenant that submits its next query when the last one
came back.  Which query, its parameters and the number of streams are
the traffic file's; the server's settings are the configuration's."""

import contextlib
import importlib
import random
import statistics
import threading
import time

GRACE_S = 60.0        # an answer may come this long after the close
NO_SPAN = lambda _name: contextlib.nullcontext()  # noqa: E731


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.ref = cell.reference
        self.server = None
        t = cell.traffic
        self.query = t["op"]
        self.streams = int(t["streams"])
        # the data seeds every stream draws from: the same count for
        # every --seed, so the reference work after the window is fixed
        self.pool = [(cell.seed * 1_000_003 + i) % (2 ** 31 - 1)
                     for i in range(int(t["seed_pool"]))]
        self.orders = []
        for i in range(self.streams):
            order = list(self.pool)
            random.Random(cell.seed * 31 + i).shuffle(order)
            self.orders.append(order)
        self.cursor = [0] * self.streams

    def setup(self):
        from spark_rapids_tpu.perf import result_cache
        from spark_rapids_tpu.server import ServerConfig, ensure_server

        if result_cache.cache_enabled():
            raise RuntimeError("the result cache must be off in this "
                               "configuration")
        self.server, created = ensure_server(
            ServerConfig(**self.cell.config["server"]))
        if not created:
            raise RuntimeError("a query server was already running")
        rec = self._one(0, NO_SPAN, time.perf_counter() + 1100.0)
        if not rec["ok"]:
            raise RuntimeError("warm-up query: %s" % rec["state"])
        self.cursor[0] = 0
        return {"ops_warmed": 1}

    def _one(self, stream, annotate, wait_until):
        order = self.orders[stream]
        data_seed = order[self.cursor[stream] % len(order)]
        self.cursor[stream] += 1
        c = self.cell
        params = self.ref.query_params(c.sizes, c.traffic["params"],
                                       data_seed)
        tenant = c.traffic["tenants"][stream % len(c.traffic["tenants"])]
        with annotate("query:" + self.query):
            ts = time.perf_counter()
            try:
                qid = self.server.submit(tenant, self.query, params)
                st = self.server.poll(
                    qid, timeout_s=max(wait_until - ts, 1.0))
            except Exception as e:    # refused at the door: no answer
                st = {"state": "refused", "error": repr(e)}
            te = time.perf_counter()
        ok = st.get("state") == "done"
        return {"op": self.query, "stream": stream, "data_seed": data_seed,
                "t_start": ts, "t_end": te, "ok": ok,
                "refused": st.get("state") == "refused",
                "state": "%s %s" % (st.get("state"), st.get("error") or ""),
                "rows": int(c.sizes["rows"]), "spans": {},
                "result": st.get("result") if ok else None}

    def window(self, seconds, annotate=None, min_ops=0, max_ops=None):
        """Every stream submits until ``seconds`` have passed (at least
        ``min_ops``, at most ``max_ops`` each); the window ends when
        the queries in flight have come back."""
        annotate = annotate or NO_SPAN
        records, lock = [], threading.Lock()
        t0 = time.perf_counter()

        def stream(i):
            n = 0
            while (max_ops is None or n < max_ops) and (
                    n < min_ops or time.perf_counter() - t0 < seconds):
                rec = self._one(i, annotate, t0 + seconds + GRACE_S)
                n += 1
                with lock:
                    records.append(rec)
                if rec["refused"]:    # a stream the server turns away
                    break             # ends; its query counts as missing

        threads = [threading.Thread(target=stream, args=(i,),
                                    name="stream-%d" % i)
                   for i in range(self.streams)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return sorted(records, key=lambda r: r["t_end"])

    def probes(self):
        """The catalog layer timed from outside: the cell's own
        generator to ``block_until_ready``, three times."""
        import jax

        spec = self.cell.traffic.get("ingest_probe")
        if not spec:
            return {}
        mod, fn = spec["generator"].split(":")
        gen = getattr(importlib.import_module(mod), fn)
        args = {k: (self.cell.sizes[v[1:]] if isinstance(v, str)
                    and v.startswith("$") else v)
                for k, v in spec["args"].items()}
        samples = []
        for i in range(3):
            t0 = time.perf_counter()
            out = gen(seed=self.pool[i % len(self.pool)], **args)
            jax.block_until_ready(out)
            samples.append((time.perf_counter() - t0) * 1e3)
            del out
        return {"host_ingest_ms": statistics.median(samples)}

    def produced(self):
        return None                  # the records hold the host rows

    def release(self):
        from spark_rapids_tpu.server import stop_server
        stop_server()
        self.server = None

    def check(self, records, _produced):
        """Every answer of the window against the reference for its
        data seed (one reference per distinct seed)."""
        c = self.cell
        numbers = {"answers_missing": sum(1 for r in records
                                          if not r["ok"]),
                   "answers_compared": 0}
        numbers.update({k: 0 for k in self.ref.LIMITS})
        done = [r for r in records if r["ok"]]
        for data_seed in sorted({r["data_seed"] for r in done}):
            inputs = self.ref.make_inputs(c.sizes, c.traffic["params"],
                                          data_seed)
            want = self.ref.answer(inputs, c.traffic["params"])
            del inputs
            for r in done:
                if r["data_seed"] != data_seed:
                    continue
                got = self.ref.from_served(r["result"])
                numbers["answers_compared"] += 1
                for name, v in self.ref.compare(got, want).items():
                    numbers[name] = max(numbers[name], v)
        return numbers
