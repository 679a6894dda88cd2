"""The Qwen3 family's weights under the name that
``tests/test_serve_tracing.py`` imports. The harness takes a
configuration's weights from ``bench/families/<family>/weights.py``."""
from bench.families.qwen3.weights import make_weights  # noqa: F401
