#!/usr/bin/env bash
# SC25 weak-scaling protocol on a TPU pod slice: per-host batch size FIXED,
# total work grows with the slice (reference: run-scripts/SC25-job-weak.sh —
# the dual of the strong-scaling script; per-rank batch constant, global
# batch = bs * ranks). Timed batches capped, val/test disabled.
#
#   ./run-scripts/tpu-weak-scaling.sh TPU_NAME ZONE DRIVER [ARGS...]
set -euo pipefail

TPU_NAME=${1:?tpu name}
ZONE=${2:?gce zone}
DRIVER=${3:?training driver .py}
shift 3

PER_HOST_BS=${PER_HOST_BS:-160}
REPO_DIR=${REPO_DIR:-\$HOME/hydragnn_tpu}

echo "weak scaling: per-host bs=${PER_HOST_BS} (global batch grows with the slice)"

# HYDRAGNN_TRACE_LEVEL stays 0: at 1 every region edge of the training loop
# drains the device first (utils/tracer.py), eight drains a step, so the
# loop runs one step at a time with no host/device overlap and the timed
# batches measure that. Set it to 1 only for a device-accurate region report.
ARGS=""
if [ "$#" -gt 0 ]; then
  ARGS=$(printf '%q ' "$@")
fi

gcloud compute tpus tpu-vm ssh "${TPU_NAME}" \
  --zone "${ZONE}" \
  --worker=all \
  --command "cd ${REPO_DIR} && \
    ${HYDRAGNN_COORDINATOR:+HYDRAGNN_COORDINATOR=${HYDRAGNN_COORDINATOR}} \
    HYDRAGNN_VALTEST=0 \
    HYDRAGNN_MAX_NUM_BATCH=${HYDRAGNN_MAX_NUM_BATCH:-5} \
    HYDRAGNN_TRACE_LEVEL=${HYDRAGNN_TRACE_LEVEL:-0} \
    python ${DRIVER} --batch_size ${PER_HOST_BS} ${ARGS}"
