"""Read-heavy serving plane (ISSUE 13).

Layers a model-serving surface over the training substrate: hot-row
caching with version-clock invalidation lives in ``kv/cache.py`` (it is a
KV concern), while this package holds what is serving-specific —
SLO-driven admission control (:mod:`.admission`) and the open-loop
synthetic load generator (:mod:`.loadgen`).
"""

from parameter_server_tpu.serve.admission import AdmissionController, ShedError
from parameter_server_tpu.serve.loadgen import LoadGenerator, LoadReport

__all__ = [
    "AdmissionController",
    "ShedError",
    "LoadGenerator",
    "LoadReport",
]
