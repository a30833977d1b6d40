#!/bin/bash
# HPO campaign over a fleet of single-chip TPU VMs — the counterpart
# of the reference's DeepHyper SLURM campaigns (reference run-scripts/
# job-omnistat-deephyper.sh + examples/multidataset_hpo_sc26/
# gfm_deephyper_multi_all_mpnn.py: one trial per allocation, search
# over mpnn_type x width x lr).
#
# TPU shape: trials are independent single-chip trainings, so the
# natural launch is N queued-resource VMs, each taking a strided slice
# of the deterministically-shuffled search grid (--worker i
# --num-workers N in the driver) — a true partition, no duplicated
# trials. The persistent compile cache (on a TPU: <checkout>/.xla_cache,
# or wherever JAX_COMPILATION_CACHE_DIR points) makes repeat
# architectures reload executables instead of recompiling.
#
# Usage:
#   TPU_PREFIX=hpo-worker N_WORKERS=4 ZONE=us-east5-a \
#     bash run-scripts/tpu-hpo-campaign.sh \
#     examples/multidataset_hpo_sc26/train_hpo.py --trials 8
set -euo pipefail

TPU_PREFIX=${TPU_PREFIX:?set TPU_PREFIX (VM names <prefix>-0..N-1)}
N_WORKERS=${N_WORKERS:?set N_WORKERS}
ZONE=${ZONE:?set ZONE}
DRIVER=${1:?usage: tpu-hpo-campaign.sh <hpo_driver.py> [args...]}
shift
# %q-quote caller args so they survive the remote shell verbatim.
ARGS=$(printf ' %q' "$@")

pids=()
for i in $(seq 0 $((N_WORKERS - 1))); do
  gcloud compute tpus tpu-vm ssh "${TPU_PREFIX}-${i}" --zone "$ZONE" \
    --command "
      cd ~/hydragnn_tpu_repo &&
      python $DRIVER$ARGS --worker ${i} --num-workers ${N_WORKERS} \
        2>&1 | tee hpo_worker_${i}.log
    " &
  pids+=($!)
done

# set -e does not cover backgrounded jobs: collect each worker's exit
# status so a failed slice fails the campaign loudly.
fail=0
for i in "${!pids[@]}"; do
  if ! wait "${pids[$i]}"; then
    echo "worker ${i} FAILED (see hpo_worker_${i}.log)" >&2
    fail=1
  fi
done
if [ "$fail" -ne 0 ]; then
  echo 'campaign FAILED: at least one worker slice did not finish' >&2
  exit 1
fi
echo 'campaign done; collect hpo_worker_*.log best lines'
