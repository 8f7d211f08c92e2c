"""Decoded tokens over decode slots offered: the engine's ``decoded_tokens``
over ``steps * n_slots``, from the window's start to the last request's end."""


def read(run):
    c = getattr(run, "counters", None)
    if not c or not c["steps"]:
        return None
    return 100.0 * c["decoded_tokens"] / (c["steps"] * run.n_slots)
