"""One reader per metric, found by the metric's name in BENCHMARK.json.
`read(run)` takes the harness's `Run` record and returns the number, or
None when the run holds nothing for it to read."""
