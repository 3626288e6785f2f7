PY ?= python

.PHONY: lint test test-fast trace-smoke scale-smoke quant-smoke disagg-smoke

# Static invariant checks (R001-R005): exits non-zero on any
# non-waived finding. tests/test_graftlint.py::test_repo_is_clean runs
# the same sweep in tier-1, so CI cannot drift from this target.
lint:
	$(PY) -m ray_tpu.tools.graftlint ray_tpu/

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q

test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# Distributed-tracing smoke: one trace_id across >=3 processes in the
# merged /api/timeline, for both entry paths (driver task chain and
# HTTP proxy -> replica).
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_tracing_distributed.py \
		-q -k 'merged or proxy'

# Quantization CPU parity subset: int8 KV token identity vs f32 (incl.
# COW / spec-decode), kernel dequant parity, fused-prefill parity and
# the quantized fuzz tier.
quant-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_paged_cache.py \
		tests/test_spec_decode.py \
		-q -m 'not slow' -k 'quant or Quant or FusedPrefill'

# Disaggregated prefill/decode smoke: token identity vs colocated
# across spec backends + int8, KV-block streaming over netaddr with
# transfer stats, cancel/failover block accounting, SLO admission,
# and streams-driven decode-pool autoscaling.
disagg-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve_disagg.py -q

# Trimmed scale_bench parity run: channel batching + pipelined
# submission ON vs OFF must produce bit-identical task results and
# object bytes (timing may differ, values may not).
scale-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_scale_smoke.py -q
