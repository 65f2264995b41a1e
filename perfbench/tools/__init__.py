"""Scripts run by hand on the chip while the benchmark is built or a cell
is added: the serving sweep, the spread of a set of runs.  The driver's
command uses neither."""
