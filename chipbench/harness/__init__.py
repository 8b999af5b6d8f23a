"""What no later PR should need to touch: argument parsing, name
resolution, window statistics, compile accounting, the trace reduction,
the tables of peaks and operation counts, and the final line."""
