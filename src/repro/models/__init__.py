from repro.models.dlrm import DLRM, DLRMConfig
