"""One module per kind of traffic loop, found by the traffic file's
``loop``. Each holds a ``Loop(run)`` that drives the served system
through set-up and the measured window and says what it did:

    boot()       what set-up needs besides the bulk log (a tail source)
    warm()       the cell's warm-up traffic; still set-up
    window()     the measured window: rec["t_window"], ["window_s"],
                 ["attempted"], ["failed"] and its own list of items
    stop()       end whatever it started (called once more at the end)
    collect()    rec["spans"], ["work_wall_s"], ["ledgers"] of the window
    done()       the items (requests, epochs) that ended in the window
    rows(item)   the served rows of one item
    jobs()       [{k, ledger, spans}] for the route check
    events()     columns (t, k, s, d) the system took in besides the
                 bulk log, for the reference; None if none
    work()       its part of the work line

``run`` is the harness's ``Run``: ``run.rest``, ``run.rt``, ``run.cfg``,
``run.traffic``, ``run.rec``, ``run.trace_start()`` / ``trace_stop()``.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"benchmark.loops.{name}").Loop
