# Reproducible test/dev environment (CPU; the virtual 8-device mesh the
# test suite uses). The reference ships Nix envs (default.nix:1-16); this
# is the container equivalent. It proves nothing about a chip: on a TPU
# host add libtpu 0.0.34 to these pins (`pip install "jax[tpu]==0.9.0"`)
# and run `python chip_smoke.py`.
#
#   docker build -t tensorframes-tpu .
#   docker run --rm tensorframes-tpu                 # run the test suite
#   docker run --rm tensorframes-tpu python __graft_entry__.py 8   # CPU sim mesh
#   docker run --rm tensorframes-tpu python chip_smoke.py --rehearse-cpu
FROM python:3.12-slim

# g++ builds the native packer/executor (ctypes .so) on first use
RUN apt-get update && apt-get install -y --no-install-recommends \
    g++ make && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/tensorframes-tpu
COPY requirements.lock ./
RUN pip install --no-cache-dir -r requirements.lock

COPY . .
RUN pip install --no-cache-dir -e .

ENV JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8

CMD ["python", "-m", "pytest", "tests/", "-q"]
