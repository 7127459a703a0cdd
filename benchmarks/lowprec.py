"""Low-precision lane of the fused megakernel.

Measures the exact integer lane (u8 taps accumulated in i16/i32, f32 only
at normalize) against the f32 lane, on gray u8 frames. Series per case:

  * ``xla-f32``      — legacy XLA path, f32 lane (the ``--compare`` norm
    reference row; CI's geomean gate runs over xla-backend rows);
  * ``xla-int``      — legacy XLA path, explicit integer lane;
  * ``fused-f32``    — megakernel, f32 lane;
  * ``fused-int``    — megakernel, integer lane.

Both lanes read the same u8 frame and write the same f32 magnitude, so
HBM bytes/px barely move; the honest integer-lane saving is accumulator
traffic, reported per row as ``accum_bytes_per_px`` from
``benchmarks.roofline_sobel.edge_traffic`` (2 B vs 4 B per intermediate
where the tap ladder licenses i16 — see DESIGN.md §11). On CPU the
interpreter makes the fused rows a correctness-level signal, not a speed
claim, same caveat as table2.

Timing uses the shared ``repro.kernels.tuning.measure_us`` harness."""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.roofline_sobel import edge_traffic
from repro.api import EdgeConfig, edge_detect
from repro.core import ladder
from repro.core.filters import get_operator
from repro.kernels.tuning import measure_us

CASES = [("sobel3", 1024), ("sobel5", 1024), ("sobel5", 2048)]
SMOKE_CASES = [("sobel3", 128), ("sobel5", 128)]


def _fused_backend() -> str:
    return "pallas-tpu" if jax.default_backend() == "tpu" else "pallas-interpret"


def run(smoke: bool = False) -> List[Dict]:
    rows = []
    rng = np.random.default_rng(0)
    fused_backend = _fused_backend()
    for operator, n in SMOKE_CASES if smoke else CASES:
        img = jnp.asarray(rng.integers(0, 256, (n, n)).astype(np.uint8))
        base = EdgeConfig(operator=operator).resolved()
        accum = ladder.accum_dtype(get_operator(operator)) or "f32"
        series = [
            ("xla-f32", "xla", "f32"),
            ("xla-int", "xla", "int"),
            ("fused-f32", fused_backend, "f32"),
            ("fused-int", fused_backend, "int"),
        ]
        ref_us = None
        for lane, backend, precision in series:
            cfg = base.replace(backend=backend, precision=precision)
            fn = jax.jit(lambda x, c=cfg: edge_detect(x, c).magnitude)
            us = measure_us(fn, img, iters=3)
            if ref_us is None:
                ref_us = us
            lane_accum = accum if precision == "int" else "f32"
            t = edge_traffic(True, rgb=False, accum=lane_accum)
            rows.append(
                {
                    "name": f"lowprec/{operator}/{n}x{n}/{lane}",
                    "us_per_call": us,
                    "backend": backend,
                    "variant": base.variant,
                    "derived": (
                        f"MPS={n * n / us:.1f};"
                        f"speedup_vs_xla_f32={ref_us / us:.2f};"
                        f"accum={lane_accum};"
                        f"accum_bytes_per_px={t['accum_bytes_per_px']:.1f};"
                        f"hbm_bytes_per_px={t['total']:.1f};"
                        f"lane={lane}"
                    ),
                    "config": {
                        "operator": operator,
                        "n": n,
                        "precision": precision,
                        "input": "gray-u8",
                    },
                }
            )
    return rows
