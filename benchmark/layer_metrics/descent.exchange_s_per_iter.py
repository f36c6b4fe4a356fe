"""Device seconds per outer iteration under the program's ``mesh.exchange``
stage: a random effect's visit under a mesh makes the residual whole on
every chip before its buckets read it and gathers the chips' solved lanes
into a coefficient matrix whole on every chip after, with the copies that
exist only to feed them (profiler trace of the first chip, ``tf_op``; layer:
mesh). It is a part of what ``descent.unstaged_s_per_iter`` holds in such a
cell. None where the program has no such stage."""

from benchmark import stages


def read(obs):
    try:
        from photon_ml_tpu.obs.stages import MESH_EXCHANGE
    except ImportError:
        return None
    return stages.part(obs, (MESH_EXCHANGE,), MESH_EXCHANGE)
