"""Percent of the slice the first device spent in collective operations, in
a descent cell on a mesh (profiler trace; layer: mesh): the fixed effect's
psum a pass and the random effects' all-gathers. The reading is
``mesh.collective_time_share``'s, by its reader; a per-layer metric names the
one end-to-end metric it moves, ``fit_s`` there and ``descent_iter_s`` here."""

from benchmark import harness

read = harness.layer_reader("mesh.collective_time_share")
