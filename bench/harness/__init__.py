"""The benchmark's harness: cell lookup, traffic generation, the
measured window, and the reduction of the profiler trace."""
