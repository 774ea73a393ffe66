"""Validate feasibility.py's peak_bytes model against the real allocator.

VERDICT r4 weak #7: ``parallel/feasibility.py``'s ``peak_bytes`` (arguments
+ temps + generated code + max(out − alias, 0)) is a hand-rolled model of
XLA's ``memory_analysis()`` that anchors the Llama-3-8B "FITS a v5e-16"
claim, but had never been cross-checked against a chip's actual high-water
mark.  This tool closes that: it AOT-compiles a mid-size single-chip body
step, reads the model's prediction, then MATERIALIZES the inputs, runs the
step for real, and compares against ``device.memory_stats()``'s
``peak_bytes_in_use``.

Run it on the chip; ``--cpu`` exercises the flow on the CPU backend (whose PJRT typically lacks memory_stats — the
tool then reports ``actual: unsupported`` and exits 0 so the CPU smoke
stays green).
"""

from __future__ import annotations

import json
import sys
import time

REPO = __import__("os").path.dirname(__import__("os").path.dirname(
    __import__("os").path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    cpu = "--cpu" in sys.argv[1:]
    if cpu:
        from parameter_server_tpu.utils.platform import force_cpu

        force_cpu()
    import jax
    import numpy as np

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.feasibility import (
        compile_body_step,
        peak_bytes_from_analysis,
    )

    backend = jax.default_backend()
    dev = jax.devices()[0]
    # mid-size so the number is well above allocator granularity but far
    # from OOM: ~110M body params, fp32, batch 8 x seq 1024
    cfg = tfm.TransformerConfig(
        vocab_size=32_768, n_layers=8, n_heads=16, n_kv_heads=8,
        d_model=1024, d_ff=4096, max_seq=1024,
        remat=True, scan_blocks=True,
    )
    mesh = mesh_lib.make_mesh((1, 1))
    t0 = time.perf_counter()
    compiled, inputs = compile_body_step(
        cfg, mesh, 8, 1024, loss_chunk=256, fsdp="none"
    )
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    predicted = peak_bytes_from_analysis(ma)

    def materialize(tree):
        return jax.tree.map(
            lambda s: jax.device_put(
                np.zeros(s.shape, s.dtype), s.sharding
            ),
            tree,
        )

    params, opt_state, emb, tokens = (materialize(t) for t in inputs)
    jax.block_until_ready((params, emb))

    def stats():
        try:
            return dict(dev.memory_stats() or {})
        except Exception:  # noqa: BLE001 — plugin may not implement it
            return {}

    before = stats()
    outs = compiled(params, opt_state, emb, tokens)
    jax.block_until_ready(outs)
    after = stats()

    record = {
        "metric": "peak_bytes_model_vs_allocator",
        "unit": "pct_delta",
        "backend": backend,
        "config": "8L/16H/1024d/4096ff vocab32k, batch8 seq1024, "
                  "scan+remat, loss_chunk 256, single device",
        "compile_s": round(compile_s, 1),
        "analysis": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        },
        "predicted_peak_bytes": predicted,
    }
    peak = after.get("peak_bytes_in_use")
    if peak is None:
        record["value"] = None
        record["actual"] = "unsupported"
        record["note"] = (
            f"{backend} PJRT exposes no memory_stats peak; model run "
            "completed, no comparison possible"
        )
    else:
        record["actual_peak_bytes"] = int(peak)
        record["bytes_in_use_before_step"] = int(
            before.get("bytes_in_use", 0)
        )
        record["bytes_in_use_after_step"] = int(after.get("bytes_in_use", 0))
        record["value"] = round(100.0 * (peak - predicted) / predicted, 2)
        record["vs_baseline"] = None
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
