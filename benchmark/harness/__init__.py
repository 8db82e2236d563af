"""The benchmark's harness: general code only. It holds no name of a configuration,
a traffic mix, a query family or a metric; each of those is a file of its own that
`registry` finds by the name `BENCHMARK.json` gives."""
