"""Plain numpy reference for ``tpcds_q5_channels`` over a database
sharded across chips (``params["chips"]``): ``tpcds_q5``'s database,
answer and comparison as they are (the query does not depend on where
its rows lie), and two byte counts of one chip.  Imports nothing of the
program.

* ``min_bytes``: the least bytes one chip reads in a query, by
  ``tpcds_q5.min_bytes``' rule, over the chips;
* ``exchange_bytes``: the least bytes one chip sends to the others in
  the web join's hash exchange: under uniform hashing (chips - 1) /
  chips of its share of web_sales (the packed key 8 B, the site 4 B)
  and of web_returns (the key 8 B, the date 4 B, the two amounts 16 B).

The window's queries draw their SALES_DATEs through ``query_params``;
the check's first ``answer`` answers every date drawn, in threads over
the one database (numpy lets go of the interpreter in its loops), and
each later call takes its answer from there.  At SF100 one answer is
some ten seconds of numpy.
"""

import importlib.util
import os
import threading
from concurrent.futures import ThreadPoolExecutor


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


Q5 = _sibling("tpcds_q5")
LIMITS = Q5.LIMITS
make_inputs, compare = Q5.make_inputs, Q5.compare
control_answer, from_served = Q5.control_answer, Q5.from_served

_DRAWN = set()          # SALES_DATEs the run's queries drew
_ANSWERS = {}           # (limit, SALES_DATE) -> future, over _OF[0]
_OF = [None]
_LOCK = threading.Lock()


def query_params(sizes, params, data_seed):
    q = Q5.query_params(sizes, params, data_seed)
    with _LOCK:
        _DRAWN.add(q["sales_date"])
    return q


def answer(inputs, params):
    """``tpcds_q5.answer`` of ``inputs``; the dates drawn so far are
    answered beside it."""
    db, limit, date = inputs["db"], inputs["limit"], inputs["sales_date"]
    with _LOCK:
        if _OF[0] is not db:
            _OF[0] = db
            _ANSWERS.clear()
        todo = sorted(d for d in _DRAWN | {date}
                      if (limit, d) not in _ANSWERS)
        if todo:
            pool = ThreadPoolExecutor(len(todo))
            for d in todo:
                _ANSWERS[(limit, d)] = pool.submit(
                    Q5.answer, {"db": db, "sales_date": d, "limit": limit},
                    params)
            pool.shutdown(wait=False)
        got = _ANSWERS[(limit, date)]
    return got.result()


def min_bytes(sizes, params):
    return Q5.min_bytes(sizes, params) / int(params["chips"])


def exchange_bytes(sizes, params):
    chips = int(params["chips"])
    share = (12 * int(sizes["web_sales"])
             + 28 * int(sizes["web_returns"])) / chips
    return share * (chips - 1) / chips
