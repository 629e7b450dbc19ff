#!/usr/bin/env bash
# SC25 strong-scaling protocol on a TPU pod slice: fixed EFFECTIVE batch
# size, per-host batch = EBS / num_hosts, a fixed number of timed batches,
# validation/test disabled (reference: run-scripts/SC25-job-strong.sh:40-78 —
# EFFECTIVE_BATCH_SIZE = 5*160*8, HYDRAGNN_MAX_NUM_BATCH=5,
# HYDRAGNN_VALTEST=0).
#
#   ./run-scripts/tpu-strong-scaling.sh TPU_NAME ZONE NUM_HOSTS DRIVER [ARGS...]
set -euo pipefail

TPU_NAME=${1:?tpu name}
ZONE=${2:?gce zone}
NUM_HOSTS=${3:?number of hosts in the slice}
DRIVER=${4:?training driver .py}
shift 4

EFFECTIVE_BATCH_SIZE=${EFFECTIVE_BATCH_SIZE:-6400}
PER_HOST_BS=$((EFFECTIVE_BATCH_SIZE / NUM_HOSTS))
REPO_DIR=${REPO_DIR:-\$HOME/hydragnn_tpu}

echo "strong scaling: EBS=${EFFECTIVE_BATCH_SIZE} hosts=${NUM_HOSTS} per-host bs=${PER_HOST_BS}"

# HYDRAGNN_TRACE_LEVEL stays 0: at 1 every region edge of the training loop
# drains the device first (utils/tracer.py), eight drains a step, so the
# loop runs one step at a time with no host/device overlap and the timed
# batches measure that. Set it to 1 only for a device-accurate region report.
# printf %q re-quotes driver args so spaces/quotes survive the remote shell
# (guarded: printf with zero operands would emit a spurious '' argument)
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
