"""Metrics and observability (L7).

`metrics.trace` (the flight recorder, the run's recorder) imports nothing
but the standard library and is loaded here; every other name is taken
from its module on first use (PEP 562), so that the package's root can
open the run's recorder before JAX or NumPy is imported.
"""

from solvingpapers_tpu.metrics.trace import (
    AnomalyMonitor,
    FlightRecorder,
    TraceEvent,
    format_mesh,
    format_summary,
    summarize_trace,
)

_LAZY = {
    "hist": ("LogHistogram",),
    "writer": ("MetricsWriter", "ConsoleWriter", "JSONLWriter", "MultiWriter",
               "PrometheusTextWriter", "Ring", "TensorBoardWriter",
               "WandbWriter", "percentiles"),
    # `metrics.mfu` is the module (its function `mfu` is taken from it)
    "mfu": ("transformer_flops_per_token", "chip_peak_flops",
            "active_param_count"),
    "hlo_cost": ("format_anatomy", "parse_hlo_costs"),
    "xla_obs": ("CompileRegistry", "HBMLedger", "device_capacity_bytes",
                "pytree_bytes", "pytree_device_bytes"),
    "mesh_obs": ("MeshObservatory", "PipelineScheduleInfo", "bubble_report",
                 "link_bandwidth_bytes_per_s", "parse_hlo_collectives",
                 "probe_stage_costs"),
    "http": ("StatusServer",),
}
_MODULE_OF = {name: mod for mod, names in _LAZY.items() for name in names}


def __getattr__(name):
    mod = _MODULE_OF.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(f"{__name__}.{mod}"), name)
    globals()[name] = value
    return value
