"""A whole benchmark run at a tiny size on the CPU: the chip check is
skipped, the registry's reduced minitron stands in for the published one,
and everything else (set-up, window, reference check, result line) runs as
on the chip."""

from __future__ import annotations

import contextlib
import io
import json
import types
from unittest import mock

from bench.harness import spec

TINY_CONFIG = {
    "name": "tiny", "arch": "minitron-4b", "reference": "decoder",
    "hidden_size": 48, "intermediate_size": 144, "num_hidden_layers": 2,
    "num_attention_heads": 3, "num_key_value_heads": 1, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 500000.0, "norm_eps": 1e-05,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "compression": {"alpha": 0.3, "q": 4, "min_dim": 8,
                    "exclude": "(?:^|/)(embed|embedding|router|gate_w|conv|dt_|A_log|D_param|norm)",
                    "break_even_only": True},
    "engine": {"n_slots": 4, "page_size": 8, "prefill_chunk": 16, "share_prefix": True,
               "decode_block": 4, "kv_pool_bytes": None,
               "prefill_group_warm": 4},
}
MIXES = {
    "open": {"loop": "open",
             "system_prompts": {"share": 0.5, "groups": 2, "length": 16},
             "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.8, "min": 4, "max": 40},
             "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 3, "max": 12}},
}
CELLS = {
    "open": {"config": "tiny", "traffic": "open", "chips": 1, "rate_rps": 12.0,
             "correct": {"logit_gap": 0.05}},
}
PEAKS = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 394e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 17179869184}


def tiny_cell(kind: str) -> spec.Cell:
    import json as _json

    bench = _json.load(open(spec.ROOT / "BENCHMARK.json"))
    e2e_names = {"open": ("ttft_p95_ms", "tpot_p95_ms", "setup_s")}[kind]
    real = {"open": "minitron4b-rsi.chat"}[kind]

    def metric(m):
        return spec.Metric(m["name"], m["unit"], m["better"], m["source"],
                           spec.load_reader(m["name"]))

    e2e = [metric(m) for m in bench["end_to_end"] if m["name"] in e2e_names]
    layer = [metric(m) for m in bench["per_layer"] if real in m.get("workloads", [real])]
    cell = CELLS[kind]
    return spec.Cell(kind, 1, cell, TINY_CONFIG, MIXES[kind], e2e, layer)


def _reduced(arch_id):
    from repro.configs.registry import get_arch

    return get_arch(arch_id, reduced=True)


@contextlib.contextmanager
def on_cpu(kind: str):
    import jax

    from bench.harness import runner, weights

    with mock.patch.object(spec, "load_cell", lambda name: tiny_cell(kind)), \
            mock.patch.object(spec, "peaks_for", lambda k: PEAKS), \
            mock.patch.object(runner, "_devices", lambda chips: jax.devices()[:1]), \
            mock.patch.object(weights, "get_arch", _reduced), \
            mock.patch.object(runner, "use_compile_cache", lambda: "off"):
        yield


def run_tiny(kind: str, seed: int = 7, seconds: float = 1.0, trace: int = 0):
    """(result line as a dict, standard output text) of one tiny run."""
    from bench.harness import runner

    args = types.SimpleNamespace(workload=kind, seed=seed, seconds=seconds, trace=trace)
    out = io.StringIO()
    with on_cpu(kind), contextlib.redirect_stdout(out):
        rc = runner.run(args, 0.0)
    assert rc == 0
    text = out.getvalue()
    return json.loads(text.strip().splitlines()[-1]), text
