"""Shared example bootstrap: repo path + the compile cache."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.runtime import enable_compile_cache  # noqa: E402

enable_compile_cache()
