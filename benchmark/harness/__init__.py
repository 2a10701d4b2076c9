"""The yardstick's general half: discovery by name, the window clock,
percentiles, the open-loop schedule, the compile counter, trace capture and
the reduction from a profiler trace to metrics."""
