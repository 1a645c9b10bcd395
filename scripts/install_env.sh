#!/bin/bash
# Python environment install for photon-tpu — the TPU-native analog of the
# reference's poetry bootstrap (/root/reference/scripts/install_env.sh).
# Uses a plain venv + pip (no poetry dependency): jax[tpu] pulls libtpu,
# so this one script replaces the reference's CUDA-wheel coordination.
#
#   ./scripts/install_env.sh [-p PROJECT_PATH]
set -euo pipefail

PROJECT_PATH="$(cd "$(dirname "$0")/.." && pwd)"
while getopts "p:" opt; do
	case "$opt" in
	p) PROJECT_PATH="$OPTARG" ;;
	*)
		echo "usage: $0 [-p PROJECT_PATH]" >&2
		exit 1
		;;
	esac
done

cd "$PROJECT_PATH"
echo "install_env.sh: installing into $PROJECT_PATH/.venv"

python3 -m venv .venv
# shellcheck disable=SC1091
source .venv/bin/activate
pip install --upgrade pip

#! Accelerator stack: jax[tpu] ships the matching libtpu wheel — the whole
#! CUDA/CuDNN/driver matrix the reference manages collapses into this line.
pip install "jax[tpu]" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html

#! Framework deps (the reference's composer/llm-foundry/flower stack is
#! re-implemented in-repo; these are the only runtime requirements)
pip install flax optax orbax-checkpoint chex einops numpy pyyaml pytest

#! Optional extras the reference also gates at runtime
pip install transformers datasets 2>/dev/null || echo "install_env.sh: HF extras skipped (offline?)"

python -c "import jax; print('install_env.sh: jax', jax.__version__, 'devices:', jax.devices())"
echo "install_env.sh: done — activate with 'source .venv/bin/activate'"
