"""Shapes as an op's HLO text in the device trace writes them."""

import re

SHAPE = re.compile(r"\b(bf16|f32|f16|s32|s8|u8|u32|pred)\[([0-9,]*)\]")


def shapes(text: str):
    """The shapes in an op's HLO text, in order: its output's, then its operands'."""
    return [tuple(int(x) for x in dims.split(",") if x) for _dt, dims in SHAPE.findall(text)]
