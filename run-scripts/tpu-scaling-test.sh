#!/bin/bash
# Throughput/scaling sweep — counterpart of the reference's
# HydraGNN-scaling-test.sh (up to 8192 GCDs, HYDRAGNN_VALTEST=0
# throughput mode). Runs a val/test-free training pass on one slice;
# repeat across slice shapes (v5p-8/16/32...) for the scaling curve.
# The benchmark proper (BENCHMARK.json, benchmarks/run.py) is sized for
# one TPU v5e chip and is not part of this sweep.
#
# Usage:
#   TPU_NAME=my-v5p-8 ZONE=us-east5-a bash run-scripts/tpu-scaling-test.sh
set -euo pipefail

TPU_NAME=${TPU_NAME:?set TPU_NAME}
ZONE=${ZONE:?set ZONE}

gcloud compute tpus tpu-vm ssh "$TPU_NAME" --zone "$ZONE" --worker=all \
  --command "
    cd ~/hydragnn_tpu_repo &&
    # throughput mode: skip val/test epochs (reference HYDRAGNN_VALTEST=0)
    HYDRAGNN_TPU_VALTEST=0 HYDRAGNN_TPU_MAX_NUM_BATCH=200 \
    python examples/qm9/qm9.py --synthetic --mols 4096 --epochs 3
  "
