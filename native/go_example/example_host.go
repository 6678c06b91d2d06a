//go:build pjrt_example

// Go host for the PJRT bridge — the cgo embedding the north star names
// ("invoke compiled XLA programs from the Go-facing API via cgo→PJRT").
// It is the line-for-line Go twin of example_host.c against the same
// pjx_* C ABI (native/pjrt_bridge.cc); the C program is the compiled,
// tested proof in this image (no Go toolchain here — see ../Makefile),
// and this file documents the cgo shape a Go embedder uses. It lives in
// its own directory so cgo does not try to compile the sibling C/C++
// sources into the package:
//
//	cd native/go_example && go build -tags pjrt_example -o example_host_go .
//	(go.mod is committed; `make go-example` at the repo root does this,
//	or reports "no Go toolchain" on images without one)
//	./example_host_go PLUGIN.so MODULE.mlirpb OPTIONS.pb [name:type:value ...]
//
// The module/options inputs are produced exactly as for the C host (see
// tests/test_chip_compile.py: jax.jit(...).lower(...) -> StableHLO bytes
// + compile-options proto), so a Go service can execute the full
// vectorized router step with zero Python in the loop.
package main

/*
#cgo LDFLAGS: -L${SRCDIR}/.. -lpjrt_bridge -Wl,-rpath,${SRCDIR}/..
#include <stdint.h>
#include <stdlib.h>
#include "pjx.h"
*/
import "C"

import (
	"fmt"
	"os"
	"strconv"
	"strings"
	"unsafe"
)

const (
	errLen  = 4096
	f32Type = 11 // PJRT_Buffer_Type_F32
)

func die(stage string, err []C.char) {
	fmt.Fprintf(os.Stderr, "%s: %s\n", stage, C.GoString(&err[0]))
	os.Exit(1)
}

func main() {
	if len(os.Args) < 4 {
		fmt.Fprintf(os.Stderr,
			"usage: %s PLUGIN.so MODULE.mlirpb OPTIONS.pb [name:type:value ...]\n",
			os.Args[0])
		os.Exit(2)
	}
	module, errM := os.ReadFile(os.Args[2])
	options, errO := os.ReadFile(os.Args[3])
	if errM != nil || errO != nil {
		fmt.Fprintln(os.Stderr, "reading module/options:", errM, errO)
		os.Exit(1)
	}
	if len(module) == 0 || len(options) == 0 {
		fmt.Fprintln(os.Stderr, "empty module or options file")
		os.Exit(1)
	}

	cerr := make([]C.char, errLen)
	plugin := C.CString(os.Args[1])
	defer C.free(unsafe.Pointer(plugin))
	h := C.pjx_load(plugin, &cerr[0], errLen)
	if h == nil {
		die("pjx_load", cerr)
	}
	defer C.pjx_unload(h)

	// client options as name:type:value triples (s=string, i=int64, b=bool)
	var names []*C.char
	var types []C.int
	var svals []*C.char
	var ivals []C.int64_t
	for _, arg := range os.Args[4:] {
		parts := strings.SplitN(arg, ":", 3)
		if len(parts) != 3 {
			fmt.Fprintln(os.Stderr, "bad option triple:", arg)
			os.Exit(2)
		}
		names = append(names, C.CString(parts[0]))
		switch parts[1] {
		case "s":
			types = append(types, 0)
			svals = append(svals, C.CString(parts[2]))
			ivals = append(ivals, 0)
		case "i":
			types = append(types, 1)
			svals = append(svals, nil)
			n, perr := strconv.ParseInt(parts[2], 10, 64)
			if perr != nil {
				fmt.Fprintln(os.Stderr, "bad int option value:", arg)
				os.Exit(2)
			}
			ivals = append(ivals, C.int64_t(n))
		case "b":
			types = append(types, 2)
			svals = append(svals, nil)
			// numeric 0/1 like the C host's atoll; malformed values are
			// rejected here (stricter than atoll's silent leading-digit
			// parse) rather than silently configuring the client as 0
			n, perr := strconv.ParseInt(parts[2], 10, 64)
			if perr != nil {
				fmt.Fprintln(os.Stderr, "bad bool option value:", arg)
				os.Exit(2)
			}
			if n != 0 {
				ivals = append(ivals, 1)
			} else {
				ivals = append(ivals, 0)
			}
		default:
			fmt.Fprintln(os.Stderr, "bad option type:", parts[1])
			os.Exit(2)
		}
	}
	var namesPtr **C.char
	var typesPtr *C.int
	var svalsPtr **C.char
	var ivalsPtr *C.int64_t
	if len(names) > 0 {
		namesPtr = &names[0]
		typesPtr = &types[0]
		svalsPtr = &svals[0]
		ivalsPtr = &ivals[0]
	}
	client := C.pjx_client_create(h, namesPtr, typesPtr, svalsPtr, ivalsPtr,
		C.size_t(len(names)), &cerr[0], errLen)
	if client == nil {
		die("pjx_client_create", cerr)
	}
	defer C.pjx_client_destroy(h, client)

	format := C.CString("mlir")
	defer C.free(unsafe.Pointer(format))
	exe := C.pjx_compile(h, client,
		(*C.char)(unsafe.Pointer(&module[0])), C.size_t(len(module)), format,
		(*C.char)(unsafe.Pointer(&options[0])), C.size_t(len(options)),
		&cerr[0], errLen)
	if exe == nil {
		die("pjx_compile", cerr)
	}
	defer C.pjx_executable_destroy(h, exe)

	// fixed f32[8] input, as in the C host
	input := [8]float32{0, 1, 2, 3, 4, 5, 6, 7}
	dims := [1]C.int64_t{8}
	buf := C.pjx_buffer_from_host(h, client, unsafe.Pointer(&input[0]),
		f32Type, &dims[0], 1, &cerr[0], errLen)
	if buf == nil {
		die("pjx_buffer_from_host", cerr)
	}
	defer C.pjx_buffer_destroy(h, buf)

	inputs := [1]unsafe.Pointer{buf}
	outputs := [8]unsafe.Pointer{}
	nout := C.pjx_execute(h, exe, &inputs[0], 1, &outputs[0], 8,
		&cerr[0], errLen)
	if nout < 0 {
		die("pjx_execute", cerr)
	}
	for i := C.long(0); i < nout; i++ {
		var out [8]float32
		n := C.pjx_buffer_to_host(h, outputs[i], unsafe.Pointer(&out[0]),
			C.size_t(unsafe.Sizeof(out)), 1, &cerr[0], errLen)
		if n < 0 {
			die("pjx_buffer_to_host", cerr)
		}
		fmt.Printf("output %d:", i)
		for j := 0; j < int(n)/4 && j < len(out); j++ {
			fmt.Printf(" %g", out[j])
		}
		fmt.Println()
		C.pjx_buffer_destroy(h, outputs[i])
	}
}
