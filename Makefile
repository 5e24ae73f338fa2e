# Build/test entry points (the pom.xml analog).

.PHONY: all native lint concheck flowcheck wirecheck statecheck test chaos chaos-shake dryrun clean

all: native

native:
	$(MAKE) -C native

# style gate failing the build — the checkstyle/scalastyle analog
# (reference pom.xml:93-141 runs both at validate, failsOnError=true)
# — plus the concurrency lock-discipline gate (tools/concheck.py),
# the resource-lifecycle gate (tools/flowcheck.py), the wire-protocol
# conformance gate (tools/wirecheck.py) and the lifecycle
# state-machine gate (tools/statecheck.py)
lint:
	python tools/lint.py
	python tools/concheck.py
	python tools/flowcheck.py
	python tools/wirecheck.py
	python tools/statecheck.py

# the concurrency gate alone: lock-order cycles/rank inversions (CK01),
# blocking-under-lock (CK02), guarded-by discipline (CK03), unranked
# locks (CK04) across sparkrdma_tpu/
concheck:
	python tools/concheck.py

# the resource-lifecycle gate alone: acquire-without-release (FC01),
# double-release (FC02), release-without-acquire (FC03), undeclared
# resources (FC04) across sparkrdma_tpu/
flowcheck:
	python tools/flowcheck.py

# the wire-protocol gate alone: pack/unpack asymmetry (WC01), MSG_TYPE
# registry integrity (WC02), opcode/handler parity (WC03), magic sizes
# (WC04), bounds discipline (WC05) across the wire surface
wirecheck:
	python tools/wirecheck.py

# the lifecycle state-machine gate alone: raw state writes (SC01),
# undeclared transitions (SC02), unguarded branch reads (SC03),
# terminal escapes (SC04), annotated-but-undeclared machines (SC05)
# across the ~13 declared machines
statecheck:
	python tools/statecheck.py

test: native lint
	python -m pytest tests/ -x -q

# the seeded chaos soak alone (faults/, conf faultInject): the full
# engine matrix — loopback / tcp-threaded / tcp-async × decode
# threads × skew — under a mixed fault spec with resourceDebug +
# lockDebug on; every run must be bit-exact or a clean
# FetchFailedError with zero leaks and zero rank violations
chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py -q \
	-p no:cacheprovider -k chaos

# the chaos soak + push drills under the deterministic schedule shaker
# (conf schedShake, utils/statemachine.py): every validated state
# transition injects a seeded 0-2ms yield to widen race windows, with
# stateDebug + lockDebug + resourceDebug all on — zero illegal
# transitions, zero leaks, zero rank violations required
chaos-shake:
	SCHED_SHAKE=20260807 JAX_PLATFORMS=cpu python -m pytest \
	tests/test_faults.py -q -p no:cacheprovider -k chaos
	SCHED_SHAKE=20260807 JAX_PLATFORMS=cpu python -m pytest \
	tests/test_push.py -q -p no:cacheprovider

dryrun:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
	python -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__ as g; g.dryrun_multichip(8); print('dryrun OK')"

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
