"""Device-resident batched inference helpers for the player hot loops.

At 64-512 concurrent envs the obs→action path must not grow with
``num_envs`` on the host side (PERF.md §5).  Two invariants enforce
that, shared by every rewired loop:

* **one h2d per vector step** — the batched obs slab is staged in a single
  :func:`jax.device_put` call against a sharding object built ONCE per run
  (:func:`obs_sharding`): reusing the sharding lets jax cache the transfer
  plan instead of re-deriving placement per key per step;
* **one blocking d2h per vector step** — every policy output the host needs
  (actions, logprobs, values, ...) is fetched in a single
  :func:`fetch_values` call, so the blocking device->host sync (which waits
  for the forward and costs one transfer) is paid once per *vector* step
  regardless of ``num_envs`` — the fetch amortization
  ``Telemetry/fetch_amortization`` tracks live.

The policy forward itself stays behind ``diag.instrument(kind="rollout")``,
which is also what counts the fetches for the amortization gauge.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple


def obs_sharding(mesh: Optional[Any] = None):
    """The reusable sharding the player stages its obs slab with: fully
    replicated over ``mesh`` when one is given (multi-device rollouts), else
    committed to the default device.  Build it once per run and pass it to
    every per-step ``jax.device_put``/``prepare_obs`` call."""
    import jax

    if mesh is not None and getattr(mesh, "devices", None) is not None and mesh.devices.size > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(mesh, PartitionSpec())
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


def fetch_values(*arrays: Any) -> Tuple[Any, ...]:
    """ONE blocking device→host fetch for every policy output the host loop
    needs — ``np.asarray`` per output would pay the link round trip per
    array.  Returns numpy arrays in argument order."""
    import jax

    return tuple(jax.device_get(arrays))
