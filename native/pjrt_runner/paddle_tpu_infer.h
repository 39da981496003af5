/* paddle_tpu native inference C API (libpaddle_tpu_infer.so).
 *
 * The linkable equivalent of the reference's
 * paddle/fluid/inference/api/paddle_inference_api.h (C API in
 * paddle/fluid/inference/capi) for the TPU-native stack: a serving
 * process creates a predictor from an exported StableHLO artifact
 * (inference.export_native) + any PJRT C-API plugin (libtpu.so, a CPU
 * plugin), then runs it on raw host buffers. No Python anywhere in the
 * path.
 *
 * Thread-safety: one PTI_Predictor may be used from one thread at a
 * time; create several predictors (sharing nothing) for concurrency —
 * the PredictorPool pattern.
 */
#ifndef PADDLE_TPU_INFER_H_
#define PADDLE_TPU_INFER_H_

#ifdef __cplusplus
extern "C" {
#endif

typedef struct PTI_Predictor PTI_Predictor;

/* Create: dlopen the plugin, build a client, compile the artifact.
 * option_kv: "key=value" client create options (may be NULL when
 * num_options == 0). Returns NULL on failure with a message in errbuf. */
PTI_Predictor* PTI_Create(const char* plugin_so, const char* artifact_dir,
                          const char* const* option_kv, int num_options,
                          char* errbuf, int errbuf_len);

int PTI_NumInputs(const PTI_Predictor* p);
int PTI_NumOutputs(const PTI_Predictor* p);

/* Fill dims[0..ndims); returns ndims, or -1 if i/max_dims is bad. */
int PTI_InputShape(const PTI_Predictor* p, int i, long long* dims,
                   int max_dims);
int PTI_OutputShape(const PTI_Predictor* p, int i, long long* dims,
                    int max_dims);

/* Dtype name ("float32", "int64", ...) — owned by the predictor. */
const char* PTI_InputDtype(const PTI_Predictor* p, int i);
const char* PTI_OutputDtype(const PTI_Predictor* p, int i);

long long PTI_InputByteSize(const PTI_Predictor* p, int i);
long long PTI_OutputByteSize(const PTI_Predictor* p, int i);

/* Run one batch: inputs[i] raw little-endian bytes of InputByteSize(i);
 * outputs[i] caller-owned buffers of OutputByteSize(i). Returns 0 on
 * success, nonzero with a message in errbuf otherwise. */
int PTI_Run(PTI_Predictor* p, const void* const* inputs,
            void* const* outputs, char* errbuf, int errbuf_len);

void PTI_Destroy(PTI_Predictor* p);

#ifdef __cplusplus
}
#endif
#endif /* PADDLE_TPU_INFER_H_ */
