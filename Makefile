# Tier-1 verification (mirrors .github/workflows/ci.yml)

PY ?= python

.PHONY: test test-http lint ci

# tier-1: everything but the http-marked end-to-end serving shard (which
# compiles a real engine per module and would slow the whole matrix)
test:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not http"

# the end-to-end HTTP serving shard (real engine behind the front door)
test-http:
	PYTHONPATH=src $(PY) -m pytest -x -q -m http

lint:
	ruff check .
	$(PY) tools/check_links.py

ci: test
