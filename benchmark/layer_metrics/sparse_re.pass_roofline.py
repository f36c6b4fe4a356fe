"""The subspace passes of the sparse random effects against the chip's
roofline (layer: random_effects): the least seconds the chip could take for
the USEFUL passes of the slice (per entity, the L-BFGS iterations it ran
times one sparse objective pass over its rows, nonzeros and support:
``benchmark/work_sparse_re.py``, summed by the runner) over the device
seconds of the program's ``re.sparse_pass`` stage. One pass an iteration is
the least any implementation needs, so extra line-search passes, lanes in
lock step, padding and a densified lane's zeros all lower it.

The runner reads an entity's iterations from the LAST visit of a unit (the
descent releases the earlier trackers) and counts every visit at them; a
first visit from the zero model runs no fewer, so the share reads low."""

from benchmark import stages, work

FAMILY = ("re.sparse_pass", "re.subspace")


def read(obs):
    c = obs.counters
    per_work = stages.part(obs, FAMILY, "re.sparse_pass")
    if not per_work or not c.get("sparse_re.useful_pass_bytes"):
        return None
    least, _ = work.least_seconds(
        c["sparse_re.useful_pass_flops"], c["sparse_re.useful_pass_bytes"],
        obs.device_kind,
    )
    return 100.0 * least / (per_work * c["work"])
