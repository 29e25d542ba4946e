"""Small readers, one function per kind of source.  A reader takes the
run's observations (`ctx`, built in benchmark/run.py `measure`) and the
`args` of the metric's data file, and returns the value, or None where
it found nothing to read: the harness then leaves the metric out."""
