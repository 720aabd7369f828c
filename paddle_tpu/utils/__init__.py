"""`paddle.utils` (reference: python/paddle/utils/)."""

from . import dlpack  # noqa: F401
from . import cpp_extension  # noqa: F401
from .compile_cache import configure_compile_cache  # noqa: F401
from .flops import flops  # noqa: F401


def try_import(module_name, err_msg=None):
    import importlib
    try:
        return importlib.import_module(module_name)
    except ImportError as e:
        raise ImportError(err_msg or f"{module_name} is required") from e


def run_check():
    """paddle.utils.run_check parity: verify the runtime works."""
    import jax

    import paddle_tpu as paddle
    x = paddle.randn([4, 4])
    y = paddle.matmul(x, x)
    y.numpy()
    n = jax.device_count()
    print(f"paddle_tpu works. devices: {n} ({jax.default_backend()})")
    return True


def deprecated(update_to="", since="", reason="", level=0):
    """Deprecation decorator (reference utils/deprecated.py): warns on
    call (level 0/1), raises on call for removed APIs (level 2), and
    prefixes the docstring with the deprecation notice."""
    import functools
    import warnings

    def wrap(fn):
        msg = f"API '{fn.__module__}.{fn.__name__}' is deprecated"
        if since:
            msg += f" since {since}"
        if update_to:
            msg += f", use '{update_to}' instead"
        if reason:
            msg += f". Reason: {reason}"

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if level == 2:
                # removed API: refuse at CALL time (decoration must not
                # crash the defining module's import)
                raise RuntimeError(msg)
            warnings.warn(msg, DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)

        inner.__doc__ = f"Warning: {msg}\n\n{fn.__doc__ or ''}"
        return inner
    return wrap


def require_version(min_version, max_version=None):
    """Check the installed framework version against [min_version,
    max_version] (reference utils/install_check-style contract):
    raises on mismatch, returns True otherwise."""
    from .. import version as _version

    def key(v):
        """(numeric tuple, is_release): '0.1.0rc1' < '0.1.0' — a
        component's LEADING digits count; a pre-release suffix anywhere
        ranks below the plain release with the same numbers."""
        import re as _re
        nums, pre = [], 1
        for p in str(v).split("."):
            m = _re.match(r"(\d*)(.*)", p)
            nums.append(int(m.group(1)) if m.group(1) else 0)
            if m.group(2):
                pre = 0
        return tuple(nums + [0] * (4 - len(nums))), pre

    if not isinstance(min_version, str) or (
            max_version is not None and not isinstance(max_version, str)):
        raise TypeError("require_version expects version strings")
    cur = key(_version.full_version)
    if cur < key(min_version):
        raise Exception(
            f"installed version {_version.full_version} < required "
            f"minimum {min_version}")
    if max_version is not None and cur > key(max_version):
        raise Exception(
            f"installed version {_version.full_version} > allowed "
            f"maximum {max_version}")
    return True
