"""The one ``shard_map`` entry point of this repo.

Callers import it from here so the keyword surface they rely on
(``mesh``, ``in_specs``, ``out_specs``, ``check_vma``) is pinned in one
place.
"""

from __future__ import annotations

from typing import Any, Callable

import jax


def shard_map(
    f: Callable,
    *,
    mesh: Any,
    in_specs: Any,
    out_specs: Any,
    check_vma: bool = True,
) -> Callable:
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
