"""AWQ W4 serving end-to-end: a quantized engine must stream greedy
tokens IDENTICALLY under the Pallas kernel (interpret mode) and the pure
jnp ``ref`` oracle, through the whole serving feature matrix — chunked
prefill × int8 KV pages × prefix sharing × ngram speculative decoding —
and through a 2-way tensor-parallel mesh, with the packed weight stream
actually smaller than the float one.

Runs in a SUBPROCESS with xla_force_host_platform_device_count=4 so the
main pytest process keeps its single real device (same pattern as
test_sharded_serving)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import json
import jax
import jax.numpy as jnp
import numpy as np
import repro.configs as C
from repro.core import quantize_params
from repro.core.qlinear import set_execution_config
from repro.distributed import serving_mesh
from repro.models import build_model
from repro.serving import GenerationEngine

# Hkv = 4 divides the 2-way mesh; head_dim=16 keeps every attention linear
# above the quantizer's min-size floor.
cfg = dataclasses.replace(C.get_smoke_config("qwen25-05b"),
                          num_heads=8, num_kv_heads=4, head_dim=16)
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
qp, report = quantize_params(params)
out = {"device_count": jax.device_count(),
       "quantized_layers": len(report.quantized)}

rng = np.random.default_rng(0)
prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
prompts = [np.concatenate([prefix,
                           rng.integers(0, cfg.vocab_size, (t,)
                                        ).astype(np.int32)])
           for t in (5, 12, 9, 3)]


def serve(pp, mesh=None, **kw):
    eng = GenerationEngine(m, pp, max_seq=64, num_slots=4, page_size=8,
                           prefill_chunk=4, mesh=mesh, **kw)
    rids = [eng.submit(p, 10, prefix_id="sys") for p in prompts]
    while not eng.idle:
        eng.step()
    done = eng.collect()
    return [[int(t) for t in done[r]] for r in rids], eng.stats()


FULL = dict(kv_quant="int8", spec_decode="ngram", spec_k=4)
MATRIX = {"plain": {}, "int8": {"kv_quant": "int8"},
          "spec": {"spec_decode": "ngram", "spec_k": 4}, "full": FULL}

ref_streams = {}
for tag, kw in MATRIX.items():
    set_execution_config(impl="ref", compute_dtype=jnp.float32)
    ref_s, st = serve(qp, **kw)
    set_execution_config(impl="kernel_interpret", compute_dtype=jnp.float32)
    ker_s, _ = serve(qp, **kw)
    ref_streams[tag] = ref_s
    out[f"nonempty_{tag}"] = all(len(s) == 10 for s in ref_s)
    out[f"identical_{tag}"] = ker_s == ref_s
out["spec_fired"] = st.draft_tokens > 0            # st is the FULL run's
out["prefix_fired"] = st.prefix_shared_pages > 0

# --- 2-way mesh, quantized params, full feature stack -------------------
set_execution_config(impl="ref", compute_dtype=jnp.float32)
sh_s, st_sh = serve(qp, mesh=serving_mesh(2), **FULL)
out["identical_sharded"] = sh_s == ref_streams["full"]
out["model_axis"] = st_sh.model_axis
# the fused kernel per shard (shard_map over the mesh's `model` axis)
set_execution_config(impl="kernel_interpret", compute_dtype=jnp.float32)
ker_sh, _ = serve(qp, mesh=serving_mesh(2), **FULL)
out["identical_sharded_kernel"] = ker_sh == ref_streams["full"]
from repro.core.packing import PackedLinear
from repro.distributed.sharding import tag_tensor_parallel
tagged = tag_tensor_parallel(qp, serving_mesh(2), cfg)
tps = [x.tp for x in jax.tree.leaves(
    tagged, is_leaf=lambda x: isinstance(x, PackedLinear))
    if isinstance(x, PackedLinear)]
out["tp_tags"] = sorted(set(str(t) for t in tps))
set_execution_config(impl="ref", compute_dtype=jnp.float32)

# --- weight stream accounting -------------------------------------------
_, st_q = serve(qp)
_, st_f = serve(params)
out["weight_bytes_float"] = st_f.weight_bytes
out["weight_bytes_awq"] = st_q.weight_bytes
out["wbpt_positive"] = st_q.weight_bytes_per_token > 0

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=".",
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_quantizer_covered_the_model(result):
    assert result["device_count"] == 4
    assert result["quantized_layers"] > 0


def test_awq_kernel_streams_match_ref_across_matrix(result):
    """Greedy kernel-vs-ref identity for every serving feature cell."""
    for tag in ("plain", "int8", "spec", "full"):
        assert result[f"nonempty_{tag}"], f"{tag}: short stream"
        assert result[f"identical_{tag}"], f"{tag}: kernel diverged from ref"
    assert result["spec_fired"] and result["prefix_fired"]


def test_awq_sharded_stream_identical(result):
    """Quantized params through the 2-way mesh: packed leaves shard and
    the greedy stream stays identical to the unsharded engine."""
    assert result["model_axis"] == 2
    assert result["identical_sharded"]


def test_awq_weight_stream_shrinks(result):
    """The per-token weight stream the paper targets actually shrinks."""
    assert result["wbpt_positive"]
    assert result["weight_bytes_awq"] < 0.6 * result["weight_bytes_float"]


def test_awq_sharded_kernel_stream_identical(result):
    """The fused kernel under the 2-way mesh runs per shard —
    column-parallel layers on their N-shard, row-parallel ones on their
    K-shard with a sum over ``model`` — and the greedy stream stays
    identical to the unsharded reference engine."""
    assert result["tp_tags"] == ["col", "row"]
    assert result["identical_sharded_kernel"]
