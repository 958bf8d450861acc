"""ScaleCom core: the paper's contribution as composable JAX modules.

- chunked:     trailing-axis chunk-wise selection primitives (the production
               "chunk-wise sort"; one op set for both layouts)
- compressors: CLT-k + baselines (true top-k, local top-k, random-k, none)
- filter:      low-pass filtered residue update (Eq. 5) + Theorem-1 beta band
- state:       per-worker residue state + fp32/bf16/fp8 codecs + chunk layouts
- plan:        per-tensor reduce planning (rates/layout/shapes/byte rule),
               cached per tree structure
- scalecom:    Algorithm 1 as a worker-axis gradient reduce (GSPMD-native),
               one layout-agnostic execute stage over the plan
- metrics:     similarity/contraction diagnostics (Figs. 2-3, Appendix A)
"""

from repro.core.compressors import CompressorConfig, compress, COMPRESSORS
from repro.core.filter import lowpass_update, beta_band
from repro.core.scalecom import ScaleComConfig, scalecom_reduce, dense_reduce
from repro.core.state import ScaleComState, init_state, residue_bytes

__all__ = [
    "CompressorConfig",
    "compress",
    "COMPRESSORS",
    "lowpass_update",
    "beta_band",
    "ScaleComConfig",
    "scalecom_reduce",
    "dense_reduce",
    "ScaleComState",
    "init_state",
    "residue_bytes",
]
