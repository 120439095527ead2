"""Shared utilities (diagnostics, metrics, tracing, native build, compile cache)."""
