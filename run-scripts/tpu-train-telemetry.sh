#!/usr/bin/env bash
# Training with telemetry capture on a TPU VM — the TPU analog of the
# omnistat-instrumented runs (reference:
# run-scripts/SC25-multibranch-omnistat.sh + job-multibranch-omnistat.sh,
# which sample GPU telemetry alongside training).
#
# Since r7 the framework carries its own unified telemetry plane
# (docs/OBSERVABILITY.md): HYDRAGNN_TELEMETRY=1 turns on the per-step
# instrumentation layer — step time, graphs/nodes/edges per second,
# padding-waste fraction, an XLA-flops-derived MFU estimate, device/host
# memory — streaming into logs/<run>/metrics.jsonl (versioned records)
# with counters scrapeable at the optional /metrics endpoint
# ("Telemetry": {"http_port": N} in the config). The legacy captures are
# kept: xprof device traces via the Profile config section
# ("Profile": {"enable": 1, "target_epoch": N} -> logs/<name>/profile),
# plus a vmstat host sampler per worker. Mid-run, touch
# logs/<run>/profile_trigger (or send SIGUSR1) on a worker to capture an
# on-demand xprof trace of the next Telemetry.profile_steps steps.
#
#   ./run-scripts/tpu-train-telemetry.sh TPU_NAME ZONE DRIVER [ARGS...]
set -euo pipefail

TPU_NAME=${1:?tpu name}
ZONE=${2:?gce zone}
DRIVER=${3:?training driver .py}
shift 3

# HYDRAGNN_TRACE_LEVEL stays 0: at 1 every region edge of the training loop
# drains the device first (utils/tracer.py), eight drains a step, so the
# loop runs one step at a time with no host/device overlap and the timed
# batches measure that. Set it to 1 only for a device-accurate region report.
REPO_DIR=${REPO_DIR:-\$HOME/hydragnn_tpu}
SAMPLE_SECS=${SAMPLE_SECS:-5}

ARGS=""
if [ "$#" -gt 0 ]; then
  ARGS=$(printf '%q ' "$@")
fi

gcloud compute tpus tpu-vm ssh "${TPU_NAME}" \
  --zone "${ZONE}" \
  --worker=all \
  --command "cd ${REPO_DIR} && \
    (vmstat -t ${SAMPLE_SECS} > telemetry_host_\$(hostname).log 2>&1 &) && \
    HYDRAGNN_TELEMETRY=${HYDRAGNN_TELEMETRY:-1} \
    HYDRAGNN_TRACE_LEVEL=${HYDRAGNN_TRACE_LEVEL:-0} \
    python ${DRIVER} ${ARGS}; \
    pkill vmstat || true"

# pull the host telemetry + the per-step metrics streams back. The metrics
# files come as a tar so each run keeps its logs/<run>/metrics.jsonl path —
# a bare scp of logs/*/metrics.jsonl would flatten every run onto one
# basename and silently overwrite all but the last
gcloud compute tpus tpu-vm scp --zone "${ZONE}" --worker=all \
  "${TPU_NAME}:${REPO_DIR}/telemetry_host_*.log" . || true
gcloud compute tpus tpu-vm ssh "${TPU_NAME}" --zone "${ZONE}" --worker=0 \
  --command "cd ${REPO_DIR} && tar cf - logs/*/metrics.jsonl 2>/dev/null" \
  > telemetry_metrics.tar || true
if [ ! -s telemetry_metrics.tar ]; then
  rm -f telemetry_metrics.tar
elif ! tar xf telemetry_metrics.tar; then
  # keep the tar: a truncated transfer may still hold salvageable records
  echo "WARNING: telemetry_metrics.tar extraction failed; tar retained" >&2
fi
