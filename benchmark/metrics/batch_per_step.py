"""Serving engine: active slots a decode step carried, mean over every
step the replica has run (`decode_slot_steps_total / decode_steps_total`
of /v1/metrics at the window's close). Moves itl_p95_ms."""


def read(run):
    eng = run.engine or {}
    steps = eng.get("decode_steps_total")
    if not steps or eng.get("decode_slot_steps_total") is None:
        return None
    return eng["decode_slot_steps_total"] / steps
