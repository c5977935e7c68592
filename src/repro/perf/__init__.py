"""Roofline tooling (loop-aware HLO cost analysis + hardware model)
and runtime-trace exporters (Chrome trace / jax.profiler)."""

from .hlo_analysis import analyze_hlo, Costs
from .roofline import (HW, roofline_terms, model_flops, RooflineReport)
from .trace import jax_profiler_trace, to_chrome_trace, write_chrome_trace
