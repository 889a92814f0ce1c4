"""Misc utilities (reference `common/utils.{h,cpp}`, `common/uuid.*`)."""

from __future__ import annotations

import logging
import os
import random
import socket
import threading
import uuid as _uuid


def jittered_backoff(base_s: float, max_s: float, attempt: int) -> float:
    """Exponential backoff with full-range jitter: 0-based `attempt` k
    yields a delay in (cap/2, cap] where cap = min(max_s, base_s * 2^k).
    Shared by the engine channel's retry loop and the failover layer so
    the two back off identically."""
    delay = min(max_s, base_s * (2 ** attempt))
    return delay * (0.5 + random.random() / 2)


def short_uuid() -> str:
    """8-char request-id suffix (reference generates short uuids for
    `method-threadid-shortuuid` service request ids, `service.cpp:44-51`)."""
    return _uuid.uuid4().hex[:8]


def generate_service_request_id(method: str) -> str:
    """Service-generated request id `method-threadid-shortuuid`
    (reference `http_service/service.cpp:44-51`)."""
    return f"{method}-{threading.get_ident() & 0xFFFF}-{short_uuid()}"


def is_port_available(port: int, host: str = "0.0.0.0") -> bool:
    """Reference `common/utils.cpp:42`."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind((host, port))
            return True
        except OSError:
            return False


def pick_free_port(host: str = "127.0.0.1") -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def get_local_ip() -> str:
    """Best-effort local IP (reference `common/utils.cpp:85` uses a resolver;
    we use the connected-UDP trick with a loopback fallback)."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def join_namespace(namespace: str, key: str) -> str:
    """etcd-style namespace prefixing (reference `common/utils.cpp:105-133`)."""
    ns = namespace.strip("/")
    return f"{ns}/{key}" if ns else key


def get_logger(name: str) -> logging.Logger:
    logger = logging.getLogger(name)
    if not logging.getLogger().handlers and not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname).1s %(name)s: %(message)s"))
        logger.addHandler(h)
        logger.setLevel(logging.INFO)
    return logger


#: The compile cache when the environment names none: one fixed path
#: inside the checkout (gitignored). The path is part of XLA's cache key,
#: so it never depends on home, temp, pid or time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def compile_cache_dir() -> str:
    """Where this process's persistent XLA compile cache lives:
    `JAX_COMPILATION_CACHE_DIR` when the environment sets it, else
    `DEFAULT_COMPILE_CACHE`. Needs no JAX, so a launcher that must stay
    off the chip can still look into the directory."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_COMPILE_CACHE


def enable_persistent_compile_cache() -> str:
    """Turn on XLA's persistent compile cache, so a restarted process
    re-warms from cached executables instead of recompiling every program.

    Placed from outside: with `JAX_COMPILATION_CACHE_DIR` set, JAX has
    already taken the directory from the environment and none is set
    here; without it the cache goes to `DEFAULT_COMPILE_CACHE`. Returns
    the directory in use. Safe to call more than once."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compile_cache_dir()
