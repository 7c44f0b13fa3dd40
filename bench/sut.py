"""The system under test: the program's engine (or a Router over one-chip
replicas) built from a configuration file and the benchmark's weights.

Only this module and the program mapping of the architecture modules
(``model_config`` and ``program_params`` of ``bench/archs/<arch>.py``)
import the program (``src/repro``); the reference, the traffic and the
metrics do not.
"""
from __future__ import annotations

import sys
from pathlib import Path

from bench import plugins

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_program():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"the program is not at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def build(cfg: dict, w: dict):
    """→ (server, engines): the engine, or a Router over
    ``serving.replicas`` one-chip replicas, and the list of its engines;
    the model and its parameters over ``w`` from the configuration's
    architecture module."""
    _import_program()
    from repro.models import build_model

    arch = plugins.arch(cfg)
    model = build_model(arch.model_config(cfg))
    params = arch.program_params(cfg, w)
    s = cfg["serving"]
    if s["sampling"] != "greedy":
        raise ValueError("only greedy serving is benchmarked")
    kwargs = dict(max_seq=s["max_seq"], num_slots=s["num_slots"],
                  page_size=s["page_size"], prefill_chunk=s["prefill_chunk"],
                  kv_quant=s["kv_cache"])
    if s.get("replicas", 1) == 1:
        from repro.serving import GenerationEngine
        engine = GenerationEngine(model, params, **kwargs)
        return engine, [engine]
    from repro.launch.specs import FleetSpec, ReplicaSpec
    router = FleetSpec(replicas=s["replicas"], replica=ReplicaSpec(
        mesh_axis=1, engine_kwargs=kwargs)).build(model, params)
    return router, list(router.replicas)
