"""The sketch functions that tests compare, each compiled as one program
with its configs, sizes and top-k static, as the services run them.

Called eagerly, these functions dispatch op by op: each op compiles for
every new shape, and a scan oracle's closure is traced again on every
call.  Arguments and results are those of the functions in ``repro.core``.
"""
import jax

from repro.core import eh, fleet, race, sann, swakde

eh_add = jax.jit(eh.eh_add, static_argnums=2)
eh_add_ref = jax.jit(eh.eh_add_ref, static_argnums=2)
sum_eh_add = jax.jit(eh.sum_eh_add, static_argnums=3)
sum_eh_add_ref = jax.jit(eh.sum_eh_add_ref, static_argnums=3)
sum_eh_query = jax.jit(eh.sum_eh_query, static_argnums=2)

race_prepare_chunk = jax.jit(race.race_prepare_chunk, static_argnums=2)
race_commit_chunk = jax.jit(race.race_commit_chunk)
race_query_batch = jax.jit(race.race_query_batch)

swakde_update_chunk = jax.jit(swakde.swakde_update_chunk, static_argnums=3)
swakde_query_batch = jax.jit(swakde.swakde_query_batch, static_argnums=3)
swakde_merge = jax.jit(swakde.swakde_merge, static_argnums=2)

sann_insert = jax.jit(sann.sann_insert, static_argnums=4)
sann_prepare_chunk = jax.jit(sann.sann_prepare_chunk, static_argnums=3)
sann_commit_chunk = jax.jit(sann.sann_commit_chunk, static_argnums=2)
sann_query_batch = jax.jit(sann.sann_query_batch, static_argnums=3)
sann_query_topk_batch = jax.jit(sann.sann_query_topk_batch,
                                static_argnums=(3, 4))

race_fleet_ingest = jax.jit(fleet.race_fleet_ingest)
race_fleet_query = jax.jit(fleet.race_fleet_query)
race_fleet_kde = jax.jit(fleet.race_fleet_kde)
swakde_fleet_ingest = jax.jit(fleet.swakde_fleet_ingest, static_argnums=(4, 5))
swakde_fleet_query = jax.jit(fleet.swakde_fleet_query, static_argnums=4)
swakde_fleet_kde = jax.jit(fleet.swakde_fleet_kde, static_argnums=4)
sann_fleet_ingest = jax.jit(fleet.sann_fleet_ingest, static_argnums=(5, 6))
sann_fleet_query = jax.jit(fleet.sann_fleet_query, static_argnums=4)
sann_fleet_query_topk = jax.jit(fleet.sann_fleet_query_topk,
                                static_argnums=(4, 5))
