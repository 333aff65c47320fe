"""repro.ingest — the async streaming front-end of the SummarizerPod.

Sources produce tagged host batches, the bounded TaggedBuffer absorbs
rate mismatch under an explicit backpressure policy (plus optional
per-session token-bucket rate limits and the watermark shedding ladder,
``repro.ingest.shedding``), and IngestPipeline double-buffers host
routing against the device step:

    Source -> TaggedBuffer -> chunks -> device_put -> ingest_routed
    (producer threads)        (overlapped with the running pod program)

The chunks are copied straight from the buffer's store, one share per
session; a ``Source`` reaches the buffer through producer threads
(``IngestPipeline.feed_from``).

Above that sits the fleet edge: ``PodRouter`` fans one tagged ingress
across pod shards, and ``repro.ingest.pubsub`` puts a partitioned,
offset-addressed log (broker + wire protocol + front-end) between
untrusted producers and the router, with exactly-once producer resume
and sync-boundary offset commits.
"""
from .buffer import PAD_SID, POLICIES, TaggedBuffer
from .pipeline import IngestPipeline, PodRouter
from .pubsub import (Publisher, PubSubBroker, PubSubFrontEnd, PubSubListener,
                     partition_of, publish_frame)
from .shedding import RUNGS, RateLimit, ShedPolicy, TokenBucket
from .sources import (MAGIC, DriftSource, ReplaySource, SocketSource, Source,
                      SubsampleSource, TaggedBatch, connect_producer,
                      send_frame)

__all__ = ["PAD_SID", "POLICIES", "TaggedBuffer", "IngestPipeline",
           "PodRouter", "MAGIC", "DriftSource", "ReplaySource",
           "SocketSource", "Source", "SubsampleSource", "TaggedBatch",
           "connect_producer", "send_frame",
           "Publisher", "PubSubBroker", "PubSubFrontEnd", "PubSubListener",
           "partition_of", "publish_frame",
           "RUNGS", "RateLimit", "ShedPolicy", "TokenBucket"]
