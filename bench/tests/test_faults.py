"""The check fails a run whose timed path is broken underneath: a served
token altered where the engine produces it.  The chip check is skipped; the
rest of the run is the real one."""

from unittest import mock

from bench.tests.tiny import run_tiny


def _altering_fused(orig):
    def fused_fn(self, greedy):
        fn = orig(self, greedy)

        def wrapped(*args):
            out = list(fn(*args))
            out[6] = out[6].at[0, 0].add(1)  # the first slot's first token of the block
            return tuple(out)

        return wrapped

    return fused_fn


def test_altered_token_fails_serving_check():
    from repro.serving.engine import Engine

    line, _ = run_tiny("open", seed=11)
    assert line["correct"] is True
    with mock.patch.object(Engine, "_fused_fn", _altering_fused(Engine._fused_fn)):
        line, _ = run_tiny("open", seed=11)
    assert line["correct"] is False, line["checks"]
