"""Streaming data plane (serving/streaming/) — the durable,
replayable ingestion path Cluster Serving had (Redis streams + Flink
consumer groups, SURVEY §3.5) and this repo's HTTP pending-table did
not: a crash dropped every queued record.

Layers (docs/streaming.md):

* `StreamLog` (log.py) — framed CRC32C append-only segments with
  fsync batching, rotation, retention, torn-tail recovery;
* `DurableStream` / `StreamHub` (stream.py) — consumer groups with
  visibility-deadline leases, durable ack cursors, dead-consumer
  replay, and `StreamBacklogFull` bounded-buffer backpressure;
* consumers (consumer.py) — both serving backends draining a stream
  as a group (worker-pool batch predict, generation token streaming);
* `open_loop` — the seeded Poisson/bursty arrival harness every
  serving stack is graded under (`tests/test_overload_harness.py`).
"""

from analytics_zoo_tpu.serving.streaming.consumer import (
    StreamConsumer,
    generation_consumer,
    predict_consumer,
)
from analytics_zoo_tpu.serving.streaming.log import StreamLog
from analytics_zoo_tpu.serving.streaming.open_loop import (
    bursty_trace,
    poisson_trace,
    run_open_loop,
)
from analytics_zoo_tpu.serving.streaming.stream import (
    DurableStream,
    StreamBacklogFull,
    StreamHub,
    StreamRecord,
)

__all__ = ["StreamLog", "DurableStream", "StreamHub", "StreamRecord",
           "StreamBacklogFull", "StreamConsumer", "predict_consumer",
           "generation_consumer", "poisson_trace", "bursty_trace",
           "run_open_loop"]
