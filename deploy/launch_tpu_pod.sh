#!/usr/bin/env bash
# TPU-VM pod-slice launcher — the reference's deploy layer, TPU-native.
#
# The reference launched training by building a Docker image, pushing it to
# IBM Cloud Container Registry, and kubectl-applying per-role (chief/ps/
# worker) Jobs + Services (SURVEY.md §2.1 rows "Dockerfile" / "K8s
# manifests" / "Submit scripts", §3.5 call stack).  SPMD on TPU needs none
# of that role choreography: every host of a pod slice runs the SAME
# command; jax.distributed.initialize() discovers peers from TPU metadata
# (launch/tpu_vm.py), and the mesh + collectives do the rest.
#
# Usage:
#   ./deploy/launch_tpu_pod.sh <tpu-name> <zone> [--preset mnist_cnn_dp8 ...]
#
# Everything after zone is passed through to the training CLI.

set -euo pipefail

TPU_NAME="${1:?usage: launch_tpu_pod.sh <tpu-name> <zone> [cli args...]}"
ZONE="${2:?usage: launch_tpu_pod.sh <tpu-name> <zone> [cli args...]}"
shift 2
CLI_ARGS=("$@")

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
PKG="distributed_tensorflow_ibm_mnist_tpu"

# 1. Ship the framework to every host of the slice (scp over gcloud ssh).
#    The native data library goes as SOURCE (native/dtm.cpp), never as this
#    machine's native/build/ binary: each host builds its own on first use.
gcloud compute tpus tpu-vm ssh "${TPU_NAME}" --zone="${ZONE}" --worker=all \
  --command="mkdir -p ~/app/native"
gcloud compute tpus tpu-vm scp --recurse \
  "${REPO_ROOT}/${PKG}" "${REPO_ROOT}/pyproject.toml" \
  "${TPU_NAME}:~/app/" --zone="${ZONE}" --worker=all
gcloud compute tpus tpu-vm scp \
  "${REPO_ROOT}/native/dtm.cpp" \
  "${TPU_NAME}:~/app/native/" --zone="${ZONE}" --worker=all

# 2. Start the identical SPMD process on every host.  No role flags, no
#    ClusterSpec: TPU metadata gives each process its slice coordinates.
gcloud compute tpus tpu-vm ssh "${TPU_NAME}" --zone="${ZONE}" --worker=all \
  --command="cd ~/app && python -m ${PKG}.launch.cli ${CLI_ARGS[*]}"
