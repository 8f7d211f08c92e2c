"""The plain reference is the one the configuration names, found by name
under ``bench/reference``, for the check and for its control alike."""

import types
from unittest import mock

import numpy as np
import pytest

from bench.harness import check_serve, spec
from bench.tests.tiny import TINY_CONFIG

DATA = spec.BENCH / "tests" / "data"


def _rec(prompt, tokens):
    return types.SimpleNamespace(req=types.SimpleNamespace(prompt=np.asarray(prompt),
                                                           tokens=list(tokens)))


def test_check_calls_the_reference_the_configuration_names():
    config = dict(TINY_CONFIG, reference="stub_reference")
    recs = [_rec([3, 4, 5], [7, 2, 9]), _rec([1, 2], [4])]
    with mock.patch.object(spec, "REFERENCES", DATA):
        stub = spec.load_reference("stub_reference")
        stub.CALLS.clear()
        # a served token's gap under the stub is its own id; 9 is the widest
        assert check_serve.gaps(None, config, recs) == (9.0, 4)
        assert stub.CALLS == [("hidden", "tiny", False), ("logits_rows", "tiny", False)] * 2
        stub.CALLS.clear()
        # the control judges the token its float8 form puts first, token 1
        worst, n = check_serve.gaps(None, config, recs, control=True)
    assert (worst, n) == (1.0, 4)
    assert ("hidden", "tiny", True) in stub.CALLS and ("logits_rows", "tiny", True) in stub.CALLS


def test_default_reference_is_the_dense_decoder():
    ref = spec.load_reference(TINY_CONFIG["reference"])
    assert ref.__file__ == str(spec.BENCH / "reference" / "decoder.py")
    assert callable(ref.hidden) and callable(ref.logits_rows)


def test_unknown_reference_stops_the_run():
    with pytest.raises(SystemExit, match="no_such_model"):
        check_serve.gaps(None, dict(TINY_CONFIG, reference="no_such_model"), [_rec([1], [2])])
