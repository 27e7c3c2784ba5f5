"""The paper's contribution end-to-end, on the sliced-segment API: a
latency-critical inference job preempts a best-effort training job on the
shared device with *bounded* delay — both jobs expose their device work as
sliced GPU-access segments (`repro.core.segments`), so a preemption waits
out at most one in-flight slice instead of a whole program, and the
admission test's epsilon comes from the *measured* per-slice profile
rather than a whole-train-step worst case.

  PYTHONPATH=src python examples/preemptive_serving.py

With ``--n-devices N`` (N > 1) the same two workloads run on a
ClusterExecutor: inference pinned to device 0, training pinned to device
N-1 (the boundary device — the admission path the cross-device analysis
guards), so the inference WCRT is computed on the multi-device platform
and the per-device MORTs show the isolation.  It exits with an error when
fewer than N JAX devices are visible, and when either job fails.
"""
import argparse

import jax.numpy as jnp

from repro.configs import get
from repro.launch.fleet import decode_workload, train_workload
from repro.launch.preemptive import check_preemptive, run_preemptive
from repro.launch.serve import InferenceEngine
from repro.runtime import enable_compile_cache, visible_devices


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-devices", type=int, default=1,
                    help="N>1: train on device N-1, infer on device 0")
    args = ap.parse_args()
    enable_compile_cache()
    n_devices = args.n_devices
    infer_dev, train_dev = 0, n_devices - 1
    # physical placement: each workload's arrays (and therefore its XLA
    # programs) live on its scheduling device
    jdevs = visible_devices(n_devices)
    # inference: one prefill slice + 4 decode-token slices per release
    engine = InferenceEngine(get("smollm-135m").reduced(), max_len=64,
                             device=jdevs[infer_dev])
    infer_wl = decode_workload("infer", engine,
                               jnp.zeros((2, 8), jnp.int32), 4, 1)
    # training: one full train step per slice, two microbatches
    train_wl = train_workload("train", get("olmo-1b").reduced(), batch=1,
                              seq_len=32, microbatches=2,
                              jdev=jdevs[train_dev])

    result = run_preemptive(infer_wl, train_wl, n_devices=n_devices,
                            infer_device=infer_dev, train_device=train_dev,
                            policy="notify")
    print(f"inference: {result['rt_completions']} jobs, "
          f"MORT {result['rt_mort_ms']:.1f}ms vs WCRT "
          f"{result['wcrt_ms']:.1f}ms, "
          f"misses {result['rt_deadline_misses']} (slices "
          f"{[round(s, 1) for s in result['rt_slice_ms']]}ms)")
    print(f"training (best-effort): {result['be_completions']} releases "
          f"alongside; longest observed slice "
          f"{result['max_slice_ms']:.1f}ms (protective bound "
          f"{result['epsilon_ms']:.0f}ms)")
    if n_devices > 1:
        morts = {d: (round(v * 1e3, 1) if v is not None else None)
                 for d, v in result["per_device_mort"].items()}
        print(f"per-device MORT (ms): {morts} "
              f"(infer on {infer_dev}, train on {train_dev})")
    check_preemptive(result)
    print("preemptive_serving OK")


if __name__ == "__main__":
    main()
