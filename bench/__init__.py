"""The chip benchmark of the DLRM serving path (``python bench/run.py``).

Everything that measures lives here: the traffic generator and its mixes,
the configurations with their plain reference, the reduction from profiler
traces to metrics, the lower-bound work counts and the table of peaks.  The
program under test is driven through ``repro.launch.serve_rec.run_pipeline``.
"""
