# kubeshare-tpu build surface (ref Makefile:1-20: per-component binaries +
# container images; here one native build + one image).
#
#   make native          build tokend/pmgr/client/shim into native/build
#   make test            run the test suite (CPU mesh)
#   make chip-smoke      start the whole system once on the TPU (fails without one)
#   make rehearse        the benchmark's whole path at a tiny size on the CPU (prints no metric)
#   make images          build the kubeshare-tpu:latest container image
#   make image-check     validate everything the Dockerfile needs, sans docker
#   make e2e-kind        kind-based end-to-end (skips cleanly without kind)

IMAGE ?= kubeshare-tpu:latest
DOCKER ?= $(shell command -v docker || command -v podman)

.PHONY: all native test chip-smoke rehearse images image-check e2e-kind tsan clean

all: native

native:
	$(MAKE) -C native

tsan:
	$(MAKE) -C native tsan

test:
	python3 -m pytest tests/ -x -q

# needs a TPU; one process holds the chip (see the script's docstring for
# --chips 4 and --interposer)
chip-smoke:
	python3 chip_smoke.py

# what `python3 -m chipbench.run` does on the chip, end to end, on the CPU:
# each prints one line with "correct": true and no metric
rehearse: native
	JAX_PLATFORMS=cpu python3 -m chipbench.tests.rehearse rate tiny
	JAX_PLATFORMS=cpu python3 -m chipbench.tests.rehearse backlog tiny_moe

images: image-check
ifeq ($(strip $(DOCKER)),)
	@echo "error: neither docker nor podman found; cannot build $(IMAGE)." >&2
	@echo "image-check passed: the build context is complete — run" >&2
	@echo "  docker build -f docker/Dockerfile -t $(IMAGE) ." >&2
	@echo "on a machine with a container runtime." >&2
	@exit 1
else
	$(DOCKER) build -f docker/Dockerfile -t $(IMAGE) .
endif

# Everything `docker build` will need, verifiable on container-less hosts:
# the native build (hermetic, vendored PJRT header) and every path the
# Dockerfile COPYs / the manifests reference.
image-check: native
	@test -f native/build/libtpushim.so.1 || { echo "missing libtpushim.so.1"; exit 1; }
	@test -f native/build/libtpushare_client.so
	@test -x native/build/tpushare-tokend
	@test -x native/build/tpushare-pmgr
	@test -f docker/Dockerfile
	@test -d kubeshare_tpu -a -d examples -a -d deploy/config
	@python3 -c "import kubeshare_tpu"
	@python3 -c "import kubeshare_tpu.cli as c; subs = c.build_parser()._subparsers._group_actions[0].choices; missing = {'collector','aggregator','configd','launcher','scheduler','simulate'} - set(subs); assert not missing, 'cli missing subcommands %s' % missing"
	@echo "image-check: ok (context complete for $(IMAGE))"

e2e-kind:
	deploy/e2e-kind.sh

clean:
	$(MAKE) -C native clean
