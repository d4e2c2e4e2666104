"""On-chip benchmark of the Thanos prune job and compressed-resident serving.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once.  Everything a cell is made of is found by name:

* ``workloads/<cell>.json``   the cell: its configuration, traffic, limits
                               and the metrics it reports;
* ``configs/<config>.json``   the model as it is run, its source and the
                               plain reference that checks it;
* ``traffic/<traffic>.json``  the parameters of the job or request mix,
                               read by the driver its ``kind`` names;
* ``metrics/<metric>.py``     one reader per metric, ``read(rec)``;
* ``references/<name>.py``    plain fp32 references, importing nothing of
                               the program: ``block_leaves(cfg, i)`` and
                               ``top_leaves(cfg)`` name and size the
                               weights (block ``i``'s own, so blocks may
                               differ), ``LINEARS`` the kernels masked and
                               packed n:m ((in, out), or (E, in, out) for a
                               stack of experts), and ``block``, ``head``
                               and ``magnitude_nm_mask`` (of one 2-D kernel)
                               compute.

The peak table (``peaks.py``), the operation and byte counts
(``costs.py``), the trace reduction (``trace.py``) and the comparisons that
decide ``correct`` live here too, so that no change to the program can
change the yardstick.
"""
