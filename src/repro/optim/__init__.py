from repro.optim.optimizers import (adamw_init, adamw_update,
                                    apply_error_feedback, compress_grads,
                                    rowwise_adagrad_init,
                                    rowwise_adagrad_update, sgdm_init,
                                    sgdm_update)
