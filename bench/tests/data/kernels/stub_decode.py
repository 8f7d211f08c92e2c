"""A stand-in kernel cost for the lookup test: the paged decode kernel's
calls, at one microsecond each."""

import re

PATTERN = re.compile(r"^%paged_decode_attention_pallas[.\s]")


def least(run, calls, peaks):
    return len(calls) * 1e-6
