"""Shared subprocess environment for the CI smokes (doctor_smoke,
fleet_smoke, mix_chaos_smoke, elastic_smoke, ...).

Every smoke spawns fresh CPU-JAX children in temp workdirs; the env
recipe they need is identical and used to be copy-pasted per script:

- force ``JAX_PLATFORMS=cpu`` — these are CPU tools: a parent here may
  spawn several JAX children, which on a chip (one process at a time)
  would fail or hang;
- put the repo first on ``PYTHONPATH``;
- run **cache-less** (``HYDRAGNN_COMPILE_CACHE=0``) so no leg is warmed
  by another smoke's entries in the shared ``<checkout>/logs/xla_cache``
  (the gates time cold starts and count compiles); pass
  ``compile_cache=True`` for a leg that deliberately exercises the
  cache, and place it with ``JAX_COMPILATION_CACHE_DIR`` in ``extra``.

Import from a sibling run-script as::

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from smoke_env import child_env
"""

import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child_env(extra=None, *, repo=_REPO, compile_cache=False,
              device_count=None):
    """The env dict for one smoke child process.

    ``extra`` overlays last (so a leg can still override anything);
    ``device_count`` rewrites ``xla_force_host_platform_device_count``
    in ``XLA_FLAGS`` for legs that need a specific virtual-device mesh
    independent of the parent's flags.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ":".join(
        p
        for p in [repo] + env.get("PYTHONPATH", "").split(":")
        if p
    )
    if not compile_cache:
        env["HYDRAGNN_COMPILE_CACHE"] = "0"
    if device_count is not None:
        env["XLA_FLAGS"] = " ".join(
            [
                f
                for f in env.get("XLA_FLAGS", "").split()
                if "xla_force_host_platform_device_count" not in f
            ]
            + ["--xla_force_host_platform_device_count=%d" % device_count]
        )
    env.update(extra or {})
    return env
