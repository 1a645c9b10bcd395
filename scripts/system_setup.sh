#!/bin/bash
# OS setup for a Cloud TPU VM (the tpu-ubuntu2204-base image family) — the
# TPU-native analog of the reference's GPU VM bootstrap
# (/root/reference/scripts/system_setup.sh, which installs CUDA 12.4 +
# CuDNN + nvidia persistence mode). On TPU none of that exists: the
# accelerator stack is libtpu, shipped as a Python wheel with jax[tpu]
# (installed by install_env.sh), so system setup reduces to build
# essentials and a few kernel knobs.
set -euo pipefail

#! Update and install the essentials (mirrors the reference's python-build
#! prerequisites)
sudo apt-get update
sudo apt-get install -y build-essential cmake ninja-build g++ \
	zlib1g-dev libssl-dev liblzma-dev libffi-dev libbz2-dev \
	libreadline-dev libsqlite3-dev bc

#! TPU runtime sanity: the libtpu driver needs /dev/accel* visible. On a
#! TPU VM this is preinstalled; fail fast with a useful message if not.
if ! ls /dev/accel* >/dev/null 2>&1 && ! ls /dev/vfio >/dev/null 2>&1; then
	echo "WARNING: no TPU device nodes (/dev/accel*) — is this a TPU VM?" >&2
fi

#! Networking for multi-host pods: the federation TCP control plane and
#! jax.distributed use the VM-internal network; raise the socket buffer
#! ceilings so DCN-sized allreduces and parameter pointers aren't throttled
#! by the Ubuntu defaults (reference tunes the GPU side via NCCL env).
sudo sysctl -w net.core.rmem_max=536870912 >/dev/null
sudo sysctl -w net.core.wmem_max=536870912 >/dev/null

#! Transparent hugepages help the host-side shm parameter plane (shm/)
#! which moves multi-GB bf16 payloads between node processes.
if [ -e /sys/kernel/mm/transparent_hugepage/enabled ]; then
	echo madvise | sudo tee /sys/kernel/mm/transparent_hugepage/enabled >/dev/null
fi

echo "system_setup.sh: TPU VM ready — run scripts/install_env.sh next"
