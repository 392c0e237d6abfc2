#!/bin/sh
# Reference-style CI ladder (reference Jenkinsfile:24-33 runs the suite
# under mpirun -n 1..8): run the whole suite at 1, 2, 4 and 8 virtual
# devices. The suite is device-count-agnostic by construction; this proves
# it the way the reference proves MPI-size-agnosticism.
set -e
cd "$(dirname "$0")/.."
for n in 1 2 4 8; do
  echo "=== suite at $n device(s) ==="
  env -u XLA_FLAGS JAX_PLATFORMS=cpu \
    HEAT_TPU_TEST_DEVICES=$n python -m pytest tests/ -x -q
done
