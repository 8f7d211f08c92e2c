"""The control, the plain reference a precision down (float8 operands), reads
above the serving limit where the program reads below it.  The chip
readings at the cells' own sizes are in PERF.md; this keeps the control
running at a size a test run holds."""


from bench.tests.tiny import on_cpu


def test_control_fails_where_program_passes():
    from bench import control

    with on_cpu("open"):
        r = control.readings("open", 5, 1.0)
    assert r["program_passes"], r
    assert r["control_fails"], r
