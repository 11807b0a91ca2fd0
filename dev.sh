#!/bin/bash
# Dev-loop runner: CPU-only JAX with 8 virtual host devices (the same setup
# tests/conftest.py forces).  The chip is never reached from here.
# Usage: ./dev.sh python -m pytest tests/ -x -q
exec env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
  "$@"
