"""paddle.utils parity (subset)."""
from . import unique_name  # noqa: F401
from . import compile_cache  # noqa: F401
