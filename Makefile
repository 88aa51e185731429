# Dev tooling (the reference uses mage targets, magefiles/*.go; this is
# the same surface as plain make).

PY ?= python
# `verify` uses pipefail, which /bin/sh (dash) lacks
SHELL := /bin/bash

.PHONY: test test-quick chaos chaos-campaign serve-dev demo native lint analyze verify image clean

# full suite on the virtual 8-device CPU mesh (tests/conftest.py)
test:
	$(PY) -m pytest tests/ -q

# fast smoke: engine parity + rules + authz only
test-quick:
	$(PY) -m pytest tests/test_engine.py tests/test_rules.py \
	  tests/test_authz.py -q

# failpoint-driven transport chaos: deterministic (no sleeps — backoff
# schedules injected), also part of the default `make test` selection.
# Slow-marked compositions (subprocess topologies) belong to the CI
# chaos job / `make chaos-campaign`, not this fast gate.
chaos:
	$(PY) -m pytest -m "chaos and not slow" -q --continue-on-collection-errors

# the seeded chaos campaign (chaos/campaign.py): full topology — 2 shard
# groups × 2-peer failover sets of subprocess engine hosts × the planner
# stack — driven by the loadgen open-loop schedule under deterministic
# fault schedules (wire-armed brownouts) and SIGKILL/restart cycles,
# with every safety invariant (never-fail-open, zero-acked-write-loss,
# no-stale-verdict, split-journal-completion, retry-amplification)
# checked after each episode. Fails on ANY violation. One seed names
# one byte-reproducible run (per-seed fault digests in the output).
CHAOS_SEEDS ?= 3
CHAOS_EPISODES ?= short
chaos-campaign:
	$(PY) -m spicedb_kubeapi_proxy_tpu.chaos.campaign \
	  --seeds $(CHAOS_SEEDS) --episodes $(CHAOS_EPISODES)

# fully self-contained demo: proxy + in-memory upstream + sample rules
# on http://127.0.0.1:8080 (the reference's `mage dev:up`+`dev:run` flow
# without a kind cluster); it prints curl examples on boot
demo:
	$(PY) -m spicedb_kubeapi_proxy_tpu.proxy.demo

# run a local dev proxy with the in-repo rule set against YOUR apiserver
# (reference `mage dev:run` runs against a kind cluster; set UPSTREAM_URL
# — e.g. a kind/minikube endpoint — or swap in --kubeconfig)
serve-dev:
	$(PY) -m spicedb_kubeapi_proxy_tpu.proxy.cli \
	  --rule-file deploy/rules.yaml \
	  --bootstrap deploy/bootstrap.yaml \
	  --upstream-url $${UPSTREAM_URL:?set UPSTREAM_URL} \
	  --bind-port 8443 --enable-debug-config

# build the serving image deploy/proxy.yaml references
# (spicedb-kubeapi-proxy-tpu:latest). CPU JAX by default; TPU node pools
# pass JAX_EXTRA=tpu. DOCKER=podman works too.
DOCKER ?= docker
JAX_EXTRA ?= cpu
image:
	$(DOCKER) build --build-arg JAX_EXTRA=$(JAX_EXTRA) \
	  -t spicedb-kubeapi-proxy-tpu:latest .

# (re)build the native graph-builder core explicitly
native:
	g++ -O3 -std=c++17 -fPIC -shared -pthread \
	  spicedb_kubeapi_proxy_tpu/native/graphcore.cpp \
	  -o spicedb_kubeapi_proxy_tpu/native/libgraphcore.so

# ruff (config in pyproject.toml) when available; this image doesn't bake
# it in, so fall back to a byte-compile pass rather than failing the
# target on a missing tool
lint:
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check spicedb_kubeapi_proxy_tpu tests bench.py; \
	elif $(PY) -c "import ruff" >/dev/null 2>&1; then \
	  $(PY) -m ruff check spicedb_kubeapi_proxy_tpu tests bench.py; \
	else \
	  echo "ruff not installed; falling back to compileall"; \
	  $(PY) -m compileall -q spicedb_kubeapi_proxy_tpu tests bench.py; \
	fi

# the invariant lint suite (tools/analysis/): five AST passes encoding
# the bug classes earlier review rounds fixed by hand — loop-blocking,
# lock-discipline, fail-closed, jit-stability, metrics-contract — as a
# hard gate. Zero unallowlisted findings or the build fails; intent is
# recorded per finding in tools/analysis/allowlist.txt. See
# docs/development.md.
analyze:
	$(PY) tools/analysis/run.py --strict

# the one command matching the harness: lint + the tier-1 pytest line
# from ROADMAP.md (same flags, same timeout, same pass-count echo).
# CHAOS=1 additionally runs the failpoint chaos suite first (a superset
# of what tier-1 already selects, but isolated: chaos failures surface
# on their own before the big run).
verify: lint analyze
	@if [ "$(CHAOS)" = "1" ]; then $(MAKE) chaos; fi
	$(PY) -m pytest -q -p no:cacheprovider tests/test_caveats.py
	$(PY) -m pytest -q -p no:cacheprovider tests/test_scaleout.py
	$(PY) -m pytest -q -p no:cacheprovider tests/test_rebalance.py
	$(PY) -m pytest -q -p no:cacheprovider tests/test_autoscale.py
	$(PY) -m pytest -q -p no:cacheprovider tests/test_tiered.py
	$(PY) -m pytest -q -p no:cacheprovider tests/test_migration.py
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$$?; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

clean:
	rm -f spicedb_kubeapi_proxy_tpu/native/libgraphcore.so
	rm -rf .jax_compile_cache .chip_smoke
	find . -name __pycache__ -type d -exec rm -rf {} +

# flake hunting: loop the suite until it fails (reference
# `mage test:e2eUntilItFails`)
test-until-it-fails:
	while $(PY) -m pytest tests/ -q; do echo "=== pass, again ==="; done
