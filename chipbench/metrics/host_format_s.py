"""Host seconds in the store objects' §3.2 format (decode before and
encode after each task: ``decode_object``, ``deserialize_segment``,
``deserialize_table``, ``partitions_to_object``, ``serialize_table`` of
``relational/table.py`` as the worker calls them), summed over the
executor threads, per query run under the trace."""
from chipbench.probes import FORMAT_FUNCS


def read(run):
    if run.probes is None or not run.traced:
        return None
    total = sum(run.probes.host_s.get(f, 0.0) for f in FORMAT_FUNCS)
    return total / len(run.traced)
