"""Serving engine: of the slots whose recurrent state a decode step is run
over (the lightning layers' d x d state a head, every layer's slabs of a
slot), the share whose state the step read and rewrote, mean over every
step the replica has run (`state_slots_moved_total / state_slots_total` of
/v1/metrics at the window's close; the engine reckons them from each
step's attend array: a slot that rides moves its state, one that does not
moves none). A program without the counters moves every slot's state
every step and reports nothing here. Moves itl_p95_ms."""


def read(run):
    eng = run.engine or {}
    total = eng.get("state_slots_total")
    if not total or eng.get("state_slots_moved_total") is None:
        return None
    return 100.0 * eng["state_slots_moved_total"] / total
