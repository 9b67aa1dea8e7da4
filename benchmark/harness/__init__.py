"""The yardstick: loading, statistics, peaks, FLOP counts, traffic and
the trace reduction. Later PRs add files beside these and edit none."""
