from repro.data.pipeline import HETERO_MIXES, DLRMBatch, DLRMQueryStream
